"""The trace reduction on a small trace of known numbers."""

import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import trace  # noqa: E402

# Two chips.  Times in the text are picoseconds from each line's
# timestamp_ns.  Chip 0: fusion.1 [1, 3) us, the kernel [2, 5) us and
# [8, 9) us inside a loop's event [1, 9) us, named by its whole HLO
# instruction as on a TPU, fusion.2 [20, 30) us, which starts after the
# traced part ends at 12 us.  Chip 1: one op [4, 6) us.  The host's bench.traced
# annotation spans [0, 12) us, bench.run_sweep [0, 6) us inside it.
TEXT = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 19000000 duration_ps: 10000000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 8000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 8000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "client_eval_kernel" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.2" } }
  event_metadata { key: 4 value { id: 4 name: "jit_fn(7)" } }
  event_metadata { key: 5 value { id: 5 name: "%while.9 = (s32[]) while(s32[] %p)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 4000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 6000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench.run_sweep" } }
}
"""


@pytest.fixture(scope="module")
def profile():
    import jax
    return jax.profiler.ProfileData.from_text_proto(TEXT)


def test_reduction_of_two_chips(profile):
    r = trace.reduce_profile(profile, chips=2)
    assert r["window_s"] == pytest.approx(12e-6)
    assert r["chips_traced"] == 2
    # chip 0: [1, 9) us busy; chip 1: [4, 6) us
    assert r["busy_per_chip_s"] == pytest.approx([8e-6, 2e-6])
    assert r["busy_s"] == pytest.approx(5e-6)
    assert r["kernel_s"] == pytest.approx(4e-6)
    assert r["kernel_launches"] == 2
    assert r["kernel_ops"] == ["client_eval_kernel"]
    assert r["ops_s"] == pytest.approx({"fusion.1": 4e-6,
                                        "client_eval_kernel": 4e-6,
                                        "%while.9": 8e-6})
    assert r["modules_s"] == pytest.approx({"jit_fn": 8e-6})
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion.1": 4e-6,
                                 "client_eval_kernel": 4e-6})
    # the loop spans the others: not among the ranked operations
    # chip 0's gaps: [9, 12) after run_sweep, [0, 1) inside it
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["no bench span open", "bench.run_sweep"]
    assert [g[1] for g in gaps] == pytest.approx([3e-6, 1e-6])


def test_reduction_of_the_first_chip_only(profile):
    r = trace.reduce_profile(profile, chips=1)
    assert r["busy_s"] == pytest.approx(8e-6)
    assert r["ops_s"]["fusion.1"] == pytest.approx(2e-6)


def test_a_trace_without_the_window_annotation_is_refused():
    import jax
    bare = TEXT[:TEXT.index("planes {\n  id: 3")]
    with pytest.raises(RuntimeError, match="bench.traced"):
        trace.reduce_profile(jax.profiler.ProfileData.from_text_proto(bare),
                             chips=1)
