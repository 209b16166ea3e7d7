"""The trace reduction on small recorded traces."""

import jax
import jax.numpy as jnp
import pytest

from tpubench import trace


def small_trace() -> trace.Trace:
    # one device, window [0, 100); ops busy on [10,30) u [25,40) u [60,70)
    dev = "/device:TPU:0"
    return trace.Trace(
        ops=[("fusion.1", f"{dev}|jit__superchunk_scan_jit(1)", 10, 30),
             ("fusion.2", f"{dev}|jit__superchunk_scan_jit(1)", 25, 40),
             ("copy.3", f"{dev}|jit_dynamic_slice(7)", 60, 70),
             ("late", f"{dev}|jit_other(2)", 95, 130)],
        modules=[("jit__superchunk_scan_jit(1)", 5, 45),
                 ("jit_dynamic_slice(7)", 58, 72),
                 ("jit_other(2)", 95, 130)],
        spans=[("tpubench.window", 0, 100),
               ("tpubench.backend_call", 0, 50),
               ("tpubench.query_encode", 40, 48),
               ("tpubench.tokenize", 70, 94)],
        window=(0, 100))


def test_busy_union_clips_to_window_and_merges_overlaps():
    tr = small_trace()
    # [10,40) + [60,70) + [95,100) = 30 + 10 + 5
    assert trace.busy_ns(tr) == 45
    assert trace.union_length([(0, 10), (5, 15), (20, 25)], 0, 100) == 20


def test_idle_gaps_and_their_names():
    tr = small_trace()
    gaps = trace.idle_gaps([(s, e) for _, _, s, e in tr.ops], *tr.window)
    assert gaps == [(0, 10), (40, 60), (70, 95)]
    # the gap [40, 60) overlaps backend_call by 10, query_encode by 8
    assert trace.name_gap(tr, 40, 60) == "tpubench.backend_call"
    assert trace.name_gap(tr, 70, 95) == "tpubench.tokenize"
    bd = trace.breakdown(tr)
    assert [g[0] for g in bd["idle_gaps"]] == [
        "tpubench.tokenize", "tpubench.backend_call",
        "tpubench.backend_call"]
    assert bd["idle_gaps"][0][1] == pytest.approx(25e-9)


def test_kernel_sums_by_program_name():
    tr = small_trace()
    assert trace.module_seconds(tr, r"superchunk_scan") == pytest.approx(
        40e-9)
    assert trace.module_seconds(tr, r"other") == pytest.approx(5e-9)
    ops = dict((k, v) for k, v in trace.breakdown(tr)["device_ops"])
    assert ops["jit__superchunk_scan_jit/fusion.1"] == pytest.approx(20e-9)


def test_idle_share_is_averaged_over_devices():
    tr = small_trace()
    tr.ops.append(("x", "/device:TPU:1|m", 0, 100))
    tr.n_devices = 2
    assert trace.busy_ns(tr) == pytest.approx((45 + 100) / 2)


def test_load_reads_spans_and_window_from_a_recorded_trace(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("tpubench.window"):
        with jax.profiler.TraceAnnotation("tpubench.backend_call"):
            jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    names = [s[0] for s in tr.spans]
    assert "tpubench.backend_call" in names
    lo, hi = tr.window
    call = [s for s in tr.spans if s[0] == "tpubench.backend_call"][0]
    assert lo <= call[1] <= call[2] <= hi
