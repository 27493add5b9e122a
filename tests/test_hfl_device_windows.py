"""The GRU rounds' windows, made on the device from the clients' series
staged once, against the host's ``windows_for_sensor``: the same values
bit for bit, the same training, one compiled program for every round."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.topology import ClusterTopology
from repro.data.traffic import (continual_split, generate, select_fl_sensors,
                                windows_for_sensor)
from repro.fl.client import ClientBatch
from repro.fl.hierarchy import (ContinualHFL, HFLRunConfig, _even_indices,
                                device_windows)


@pytest.fixture(scope="module")
def setting():
    ds = generate(num_days=3, n_sensors=12, seed=0)
    sensors = select_fl_sensors(ds, per_cluster=1, seed=0)     # 4 clients
    n = len(sensors)
    topo = ClusterTopology(assign=np.array([0, 0, 1, 1]), n_devices=n,
                           n_edges=2, lam=np.ones(n), r=np.ones(2), l=2)
    run = HFLRunConfig(rounds=2, local_epochs=1, max_batches=2,
                       train_days=1, val_days=1, max_val_windows=32)
    return get_config("gru-traffic"), ds, sensors, topo, run


def oracle(ds, sensors, run, round_idx):
    """The round's X, y, Xv, yv built on the host, client by client."""
    tr, va = continual_split(ds, round_idx, run.train_days, run.val_days,
                             run.shift_steps)
    out = [[], [], [], []]
    for s in sensors:
        X, y = windows_for_sensor(ds, int(s), tr.start, tr.stop, run.history)
        Xv, yv = windows_for_sensor(ds, int(s), va.start, va.stop,
                                    run.history)
        idx = _even_indices(len(Xv), run.max_val_windows)
        for acc, a in zip(out, (X, y, Xv[idx], yv[idx])):
            acc.append(a)
    return tuple(np.stack(a) for a in out)


class OracleHFL(ContinualHFL):
    """The runner fed the host oracle's windows."""

    def _round_data(self, round_idx):
        X, y, Xv, yv = (jnp.asarray(a) for a in
                        oracle(self.ds, self.sensors, self.run, round_idx))
        return ClientBatch(X=X, y=y), ClientBatch(X=Xv, y=yv)


@pytest.mark.parametrize("round_idx", [0, 1, 5])
def test_device_windows_equal_the_host_oracle(setting, round_idx):
    cfg, ds, sensors, topo, run = setting
    hfl = ContinualHFL(cfg, ds, sensors, topo, run)
    train, val = hfl._round_data(round_idx)
    want = oracle(ds, sensors, run, round_idx)
    got = (train.X, train.y, val.X, val.y)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == np.float32
        assert g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), w)


@pytest.mark.parametrize("mode", ["hier", "flat"])
def test_rounds_match_a_run_fed_the_oracle(setting, mode):
    cfg, ds, sensors, topo, run = setting
    dev = ContinualHFL(cfg, ds, sensors, topo, run, mode=mode)
    host = OracleHFL(cfg, ds, sensors, topo, run, mode=mode)
    res_dev, res_host = dev.run_rounds(2), host.run_rounds(2)
    np.testing.assert_array_equal(res_dev.mse, res_host.mse)
    np.testing.assert_array_equal(res_dev.train_loss, res_host.train_loss)
    for a, b in zip(jax.tree.leaves(dev.params),
                    jax.tree.leaves(host.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_window_program_compiles_once_across_rounds(setting):
    cfg, ds, sensors, topo, run = setting
    device_windows.clear_cache()
    hfl = ContinualHFL(cfg, ds, sensors, topo, run)
    for t in (0, 1, 5):                  # three different offsets
        hfl._round_data(t)
    assert device_windows._cache_size() == 1


def test_round_past_the_data_raises_before_dispatch(setting):
    cfg, ds, sensors, topo, run = setting
    hfl = ContinualHFL(cfg, ds, sensors, topo, run)
    last = (ds.num_steps - (run.train_days + run.val_days) * 288) \
        // run.shift_steps
    hfl._round_data(last)
    device_windows.clear_cache()
    with pytest.raises(ValueError, match="exceeds dataset length"):
        hfl._round_data(last + 1)
    assert device_windows._cache_size() == 0
