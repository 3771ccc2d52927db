"""Which device each piece runs on, checked without a chip.

* Kernel ops choose the Pallas interpreter only on the CPU backend; on a
  backend that is neither ``cpu`` nor ``tpu`` they raise instead of
  running interpreted and passing for a chip run.
* ``prepare_mosaic`` finds JAX's Mosaic rule table, or names the moved
  table in a typed error.
* The serve daemon pins pool worker ``i`` to TPU chip ``i`` through
  libtpu's visibility variables, refuses a pool larger than the host's
  chip count with a typed error, and passes a parent's
  ``JAX_PLATFORMS=cpu`` through untouched (the tests' own setting).
"""

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import platform
from repro.kernels.client_eval import ops as ce_ops
from repro.kernels.server_round import ops as sr_ops
from repro.serve import daemon


def test_interpret_mode_on_cpu():
    assert jax.default_backend() == "cpu"
    assert platform.interpret_mode() is True


def test_interpret_mode_off_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert platform.interpret_mode() is False


@pytest.mark.parametrize("backend", ["gpu", "cuda", "rocm", "METAL"])
def test_ops_refuse_interpret_mode_elsewhere(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(platform.UnsupportedPlatformError, match=backend):
        platform.interpret_mode()
    K, W = 4, 3
    pe, ye = ce_ops.extend_stream(jnp.ones((K, 8)), jnp.ones(8), W)
    with pytest.raises(platform.UnsupportedPlatformError):
        ce_ops.client_eval(pe, ye, jnp.int32(0), jnp.int32(W),
                           jnp.zeros(K), loss_scale=4.0, window=W)
    with pytest.raises(platform.UnsupportedPlatformError):
        sr_ops.server_plan(jnp.zeros(K), jnp.zeros(K), jnp.ones((K, K), bool),
                           jnp.ones(K), 3.0, jnp.zeros(K), 0.1)


def test_prepare_mosaic_gives_the_barrier_a_rule():
    from jax._src.pallas.mosaic import lowering as mosaic
    platform.prepare_mosaic()
    rules = mosaic.lowering_rules[mosaic.tpu_core.KernelType.TC]
    assert jax.lax.optimization_barrier_p in rules


def test_prepare_mosaic_names_a_moved_rule_table(monkeypatch):
    from jax._src.pallas.mosaic import lowering as mosaic
    monkeypatch.delattr(mosaic, "lowering_rules")
    with pytest.raises(platform.MosaicRuleError, match=jax.__version__):
        platform.prepare_mosaic.__wrapped__()


def test_worker_env_pins_one_chip_per_worker():
    base = {"PATH": "/bin", "HOME": "/home/u"}
    for wid in range(4):
        env = daemon.worker_env(wid, environ=base, chips=4)
        assert env["TPU_VISIBLE_CHIPS"] == str(wid)
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert env["JAX_PLATFORMS"] == "tpu"    # no silent CPU fallback
        assert env["PATH"] == "/bin" and env["HOME"] == "/home/u"
    ports = {daemon.worker_env(w, environ=base, chips=4)["TPU_PROCESS_PORT"]
             for w in range(4)}
    assert len(ports) == 4                 # no two workers share a port
    assert "TPU_VISIBLE_CHIPS" not in base  # the parent's env is untouched


def test_worker_env_explicit_tpu_platform_still_pins():
    env = daemon.worker_env(1, environ={"JAX_PLATFORMS": "tpu,cpu"},
                            chips=4)
    assert env["TPU_VISIBLE_CHIPS"] == "1"
    assert env["JAX_PLATFORMS"] == "tpu,cpu"


def test_worker_env_takes_the_parents_visible_chips_in_order():
    parent = {"TPU_VISIBLE_CHIPS": "2,3"}
    assert daemon.worker_env(0, environ=parent, chips=4)[
        "TPU_VISIBLE_CHIPS"] == "2"
    assert daemon.worker_env(1, environ=parent, chips=4)[
        "TPU_VISIBLE_CHIPS"] == "3"
    with pytest.raises(daemon.NoChipForWorker, match="chip #2"):
        daemon.worker_env(2, environ=parent, chips=4)


def test_worker_env_pool_larger_than_host_fails_typed():
    with pytest.raises(daemon.NoChipForWorker, match="chip #4"):
        daemon.worker_env(4, environ={}, chips=4)
    with pytest.raises(daemon.NoChipForWorker, match="chip #1"):
        daemon.worker_env(1, environ={}, chips=1)


def test_worker_env_cpu_platform_passes_through():
    parent = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": "src"}
    for chips in (0, 1, 4):
        for wid in (0, 7):
            assert daemon.worker_env(wid, environ=parent,
                                     chips=chips) == parent


def test_worker_env_host_without_chips_passes_through():
    assert daemon.worker_env(3, environ={"A": "1"}, chips=0) == {"A": "1"}


def test_spawned_worker_env_under_the_tests(monkeypatch):
    """What ``_spawn_worker_subprocess`` hands ``Popen`` in this process:
    the tests run with ``JAX_PLATFORMS=cpu``, which reaches the worker."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    env = daemon.worker_env(2)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in env


def test_host_tpu_chips_reads_pci_ids_and_device_nodes(tmp_path):
    sysfs, dev = tmp_path / "pci", tmp_path / "dev"
    (dev / "vfio").mkdir(parents=True)
    groups = tmp_path / "groups"

    def pci(name, vendor, device, group=None):
        d = sysfs / name
        d.mkdir(parents=True)
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
        if group is not None:
            (groups / group).mkdir(parents=True, exist_ok=True)
            (d / "iommu_group").symlink_to(groups / group)

    # four v5e chips on the bus; only two have a vfio node (a slice of
    # the host), and only those count
    for i, group in enumerate(("7", "8", "9", "10")):
        pci(f"0000:00:0{4 + i}.0", "0x1ae0", "0x0063", group)
    (dev / "vfio" / "8").touch()
    (dev / "vfio" / "10").touch()
    pci("0000:00:09.0", "0x1ae0", "0x0042")     # Google, not a TPU
    pci("0000:00:0a.0", "0x8086", "0x0063")     # another vendor
    (sysfs / "0000:00:0b.0").mkdir()            # unreadable entry
    assert daemon.host_tpu_chips(str(sysfs), str(dev)) == 2
    assert daemon.host_tpu_chips(str(tmp_path / "missing"), str(dev)) == 0


def test_host_tpu_chips_counts_accel_nodes_without_iommu(tmp_path):
    sysfs, dev = tmp_path / "pci", tmp_path / "dev"
    for i in range(4):                          # older chips: /dev/accel<i>
        d = sysfs / f"0000:00:0{4 + i}.0"
        d.mkdir(parents=True)
        (d / "vendor").write_text("0x1ae0\n")
        (d / "device").write_text("0x005e\n")
    dev.mkdir()
    for i in range(3):
        (dev / f"accel{i}").touch()
    assert daemon.host_tpu_chips(str(sysfs), str(dev)) == 3


def test_pool_start_failure_kills_the_workers_already_started():
    """A pool whose last worker has no chip fails ``start`` with the typed
    error, and the workers spawned before it do not stay up holding
    their chips."""
    started = []

    class Handle:
        killed = False

        def kill(self):
            self.killed = True

    def factory(worker_args, epoch):
        wid = worker_args["worker_id"]
        daemon.worker_env(wid, environ={}, chips=2)   # raises for wid 2
        h = Handle()
        h.streams = {}
        started.append(h)
        return h

    d = daemon.ServeDaemon(workers=3, worker_factory=factory)
    with pytest.raises(daemon.NoChipForWorker, match="chip #2"):
        d.start()
    assert len(started) == 2 and all(h.killed for h in started)
