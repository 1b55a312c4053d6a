"""The `sharding` layer's readers, shard.collective_share and
shard.host_s, on a small two-chip trace in the profiler's own format."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchkit import spec, trace  # noqa: E402

# two chips (ns): on TPU:0 a kernel 1000-5000, then the window's psum
# 5000-6000 (an all-reduce) and an all-gather 6000-6500; on TPU:1 the
# kernel 1000-3000 and the psum 3000-6000, the chip waiting for TPU:0;
# host: bench.ensemble 1000-8000 holding repro.dispatch.place 1100-1400
# (a nested place 1200-1300 in it) and repro.collect.gather 7000-7600
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%ssa_window_call.3 = (f32[65536,2]{1,0}) custom-call(f32[65536,2]{1,0} %p.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%psum.63 = f32[3,4,2]{2,1,0:T(4,128)S(1)} all-reduce(%dynamic_update_slice.140), channel_id=1, replica_groups={{0,1}}" } }
  event_metadata { key: 3 value { id: 3 name: "all-gather-start.2" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%ssa_window_call.3 = (f32[65536,2]{1,0}) custom-call(f32[65536,2]{1,0} %p.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce = (s32[4]{0:T(128)}, s32[4]{0:T(128)}) all-reduce(%get-tuple-element.1390, %get-tuple-element.1391), channel_id=1" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 7000000 }
    events { metadata_id: 2 offset_ps: 1100000 duration_ps: 300000 }
    events { metadata_id: 2 offset_ps: 1200000 duration_ps: 100000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 600000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.ensemble" } }
  event_metadata { key: 2 value { id: 2 name: "repro.dispatch.place" } }
  event_metadata { key: 3 value { id: 3 name: "repro.collect.gather" } }
}
"""


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData

    return trace.from_profile(ProfileData.from_text_proto(XSPACE))


class _Run:
    def __init__(self, tr, ensembles=2):
        self.trace = tr
        self.ensembles = [None] * ensembles


def _read(metric, run):
    return spec.load_module("metrics", metric).read(run)


def test_collective_opcodes():
    mod = spec.load_module("metrics", "shard.collective_share")
    assert mod.is_collective("%psum.52 = s32[]{:T(128)} all-reduce(%r.70)")
    assert mod.is_collective("%all-reduce-start.1 = f32[4]{0} "
                             "all-reduce-start(%a)")
    assert mod.is_collective("collective-permute-done.4")
    assert not mod.is_collective("%ssa_window_call.3 = (f32[8,2]{1,0}) "
                                 "custom-call(f32[8,2]{1,0} %p.1)")
    assert not mod.is_collective("fusion.7")


def test_collective_share_is_the_mean_over_chips(tr):
    # TPU:0: collectives 1500 of 5500 busy; TPU:1: 3000 of 5000
    share = _read("shard.collective_share", _Run(tr))
    assert share == pytest.approx(100 * (1500 / 5500 + 3000 / 5000) / 2)


def test_collective_share_without_collectives_reads_none(tr):
    kernels_only = {d: [o for o in ops if "custom-call" in o[0]]
                    for d, ops in tr.device_ops.items()}
    bare = trace.Trace(device_ops=kernels_only, host_spans=tr.host_spans,
                       t0_ns=tr.t0_ns, t1_ns=tr.t1_ns)
    assert _read("shard.collective_share", _Run(bare)) is None
    assert _read("shard.collective_share", _Run(None)) is None


def test_host_s_is_the_union_of_place_and_gather_per_ensemble(tr):
    # place 1100-1400 (the nested 1200-1300 counted once), gather
    # 7000-7600: 900 ns over 2 ensembles
    assert _read("shard.host_s", _Run(tr)) == pytest.approx(900e-9 / 2)


def test_host_s_without_its_spans_reads_none(tr):
    bare = trace.Trace(device_ops=tr.device_ops,
                       host_spans=[h for h in tr.host_spans
                                   if not h[0].startswith("repro.")],
                       t0_ns=tr.t0_ns, t1_ns=tr.t1_ns)
    assert _read("shard.host_s", _Run(bare)) is None
    assert _read("shard.host_s", _Run(None)) is None
