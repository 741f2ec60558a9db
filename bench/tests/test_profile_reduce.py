"""The trace reduction: idle share, kernel time, breakdown."""

import profile_reduce as pr

# (name, start_ns, duration_ns): two overlapping ops, a gap, a kernel
HOP = ("%hop_adc.6 = f32[1024,1,1,64]{3,2,1,0} custom-call(s32[1024,1,1,64] "
       "%and_select_fusion.2), custom_call_target=\"tpu_custom_call\"")
HOP_FS = "%hop_adc_fs.3 = s32[16,1,1,64]{3,2,1,0} custom-call(s32[16,1,1,64] %a)"
EVENTS = [("fusion.1", 0, 100), ("fusion.2", 50, 100),
          (HOP, 300, 200), (HOP, 600, 100)]
HOST = [("bench.search", 140, 170), ("bench.fetch", 500, 95)]


def test_union_and_busy():
    assert pr.union(EVENTS) == [[0, 150], [300, 500], [600, 700]]
    assert pr.busy_ns(EVENTS) == 450.0


def test_clip_to_window():
    assert pr.clip(EVENTS, 100, 650) == [
        ("fusion.2", 100, 50), (HOP, 300, 200), (HOP, 600, 50)]


def test_kernel_time_by_name():
    """Whole instruction names: the u8 and fs4 kernels' events stay apart."""
    k = pr.matching(EVENTS + [(HOP_FS, 800, 10)], ("hop_adc",))
    assert len(k) == 2 and sum(d for _, _, d in k) == 300
    assert pr.matching(EVENTS + [(HOP_FS, 800, 10)], ("hop_adc_fs",)) == [
        (HOP_FS, 800, 10)]


def test_op_names_are_short():
    assert pr.op_name(HOP) == "hop_adc.6 custom-call"
    assert pr.op_base(HOP_FS) == "hop_adc_fs"
    assert pr.op_name("fusion.1") == "fusion.1"


def test_top_ops():
    assert pr.top_ops(EVENTS, 2) == [["hop_adc.6 custom-call", 3e-7],
                                     ["fusion.1", 1e-7]]


def test_idle_gaps_named_by_host_span():
    gaps = pr.idle_gaps(EVENTS, HOST, 0, 800)
    assert gaps == [["bench.search", 150e-9], ["bench.fetch", 1e-7],
                    ["untraced host work", 1e-7]]


def test_recorded_trace_is_read(tmp_path):
    """A trace recorded here (CPU): the benchmark's host spans are found."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.search"):
        jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    jax.profiler.stop_trace()
    _, host, summary = pr.load(pr.find_xplane(str(tmp_path)))
    assert any(name == "bench.search" and d > 0 for name, _, d in host)
    assert summary
