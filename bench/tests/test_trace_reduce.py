"""The trace reduction on a small hand-made trace whose answer is known,
written with the plane, line, program and op names a TPU v5e trace of
the engine carries (``jit_batched_decode(N)`` modules, HLO-text op names
such as ``%paged_attention.9 = ... custom-call(...)``, a ``while`` that
spans the layer loop's ops)."""
import pytest
from jax.profiler import ProfileData

from bench import trace_reduce

# device: decode program 0-10 us running a while loop 1-9.5 us that holds
# a kernel 2-4 us and an op 4-9 us;
# idle 10-15 us; prefill program 15-20 us with a kernel 16-18 us; idle
# 20-30 us. host: a dispatch 12-16 us (running when the device resumes at
# 15 us) and a transfer 21-24 us.
HAND = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 15000000 duration_ps: 5000000 }
  }
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 6 offset_ps: 1000000 duration_ps: 8500000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 16000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 18000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_batched_decode(7)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_chunk_prefill(9)" } }
  event_metadata { key: 3 value { id: 3 name: "%paged_attention.9 = bf16[2,8,4,128]{3,2,1,0} custom-call(s32[2,8]{1,0} %p)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.3 = bf16[2,2560]{1,0} fusion(bf16[2,2560]{1,0} %paged_attention.9)" } }
  event_metadata { key: 6 value { id: 6 name: "%while.12 = (s32[]) while((s32[]) %t), condition=%c, body=%b" } }
  event_metadata { key: 5 value { id: 5 name: "%paged_attention_prefill.7 = bf16[1,8,512,128]{3,2,1,0} custom-call(s32[1,8]{1,0} %q)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python3"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 21000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "Execute jit_chunk_prefill" } }
  event_metadata { key: 2 value { id: 2 name: "TransferFromDevice" } }
}
"""


def test_hand_trace():
    red = trace_reduce.reduce(
        trace_reduce.load(ProfileData.from_text_proto(HAND)), 30e-6)
    # ops cover 1-9.5 and 16-20 us
    assert red["busy_s"] == pytest.approx(12.5e-6)
    assert red["modules"]["jit_batched_decode"] == {"s": pytest.approx(10e-6),
                                                    "count": 1}
    # a fusion that reads the kernel's output is not the kernel
    assert red["kernels"]["jit_batched_decode/paged_attention"]["count"] == 1
    assert "jit_batched_decode/paged_attention_prefill" not in red["kernels"]
    k = red["kernels"]["jit_chunk_prefill/paged_attention_prefill"]
    assert k["s"] == pytest.approx(2e-6) and k["count"] == 1
    # the only gap between ops, 9.5-16 us, ends inside the dispatch
    assert red["breakdown"]["idle_gaps"] == [
        ["Execute jit_chunk_prefill", pytest.approx(6.5e-6)]]
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fusion.3 bf16[2,2560]"] == pytest.approx(7e-6)
    assert not any(k.startswith("while") for k in ops)


def test_reduce_dir_times_the_kernels_it_is_given(tmp_path):
    """A kernel that is not among ``reduce``'s default names (a family's
    own, from its ``cost.KERNELS``) is timed per program once
    ``reduce_dir`` is given its name."""
    text = HAND.replace("%paged_attention.9", "%latent_attention.9")
    path = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    plain = trace_reduce.reduce(trace_reduce.load(str(path)), 30e-6)
    assert "jit_batched_decode/latent_attention" not in plain["kernels"]
    red = trace_reduce.reduce_dir(str(tmp_path), 30e-6,
                                  ("latent_attention",
                                   "paged_attention_prefill"))
    assert red["kernels"]["jit_batched_decode/latent_attention"] == {
        "s": pytest.approx(2e-6), "count": 1}
    assert "jit_chunk_prefill/paged_attention_prefill" in red["kernels"]
