"""The trace reduction, on a small trace recorded on a v5e (one prefill
and one decode step of yi-6b.chat, cut from a whole window)."""
import json
import os
import types

import pytest

from bench import devtrace, harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_small.json")


@pytest.fixture(scope="module")
def tr():
    with open(DATA) as f:
        return devtrace.DeviceTrace.from_json(json.load(f))


def test_names_as_the_trace_writes_them():
    assert devtrace.op_name(
        "%bsr_matmul_stacked.13 = f32[16,64000]{1,0:T(8,128)S(1)} "
        "custom-call(...)") == "bsr_matmul_stacked"
    assert devtrace.op_name("%while.1 = (s32[]{:T(128)}, ...)") == "while"
    assert devtrace.op_name("%copy-done = f32[2,4096]") == "copy-done"
    assert devtrace.program_name(
        "jit_decode_step_paged(8381848597439906979)") == "jit_decode_step_paged"


def test_programs_and_kernel_calls(tr):
    assert len(tr.program_times(devtrace.PREFILL_PROGRAM)) == 2
    (step,) = tr.program_times(devtrace.DECODE_PROGRAM)
    assert step == pytest.approx(0.039757958)
    # 32 layers x 7 projections + the head, in each of the three programs
    n = sum(1 for o in tr.ops if o[0] == devtrace.BSR_KERNEL)
    assert n == 3 * (32 * 7 + 1)
    total = tr.op_time(devtrace.BSR_KERNEL)
    dec = tr.op_time(devtrace.BSR_KERNEL, within=devtrace.DECODE_PROGRAM)
    pre = tr.op_time(devtrace.BSR_KERNEL, within=devtrace.PREFILL_PROGRAM)
    assert dec + pre == pytest.approx(total)
    assert 0 < dec < step


def test_busy_is_a_union_inside_the_window(tr):
    end = max(e for _, _, e in tr.ops)
    busy = tr.busy_s(0.0, end)
    # the union never exceeds the window nor the summed op times, and
    # covers at least the kernel's
    total = sum(e - s for _, s, e in tr.ops)
    assert 0 < busy <= end and busy <= total
    assert busy >= tr.op_time(devtrace.BSR_KERNEL)
    assert tr.busy_s(0.0, end / 2) + tr.busy_s(end / 2, end) == \
        pytest.approx(busy)


def test_breakdown_names_gaps_by_the_host_phase(tr):
    end = max(e for _, _, e in tr.ops)
    spans = [("decode_step", 0.1, 0.8, {}), ("step.gather", 0.12, 0.75, {})]
    b = tr.breakdown(spans, end, top=3)
    assert b["device_ops"][0][0] == devtrace.BSR_KERNEL
    assert len(b["idle_gaps"]) == 3
    assert b["idle_gaps"][0][0] == "step.gather"
    assert b["idle_gaps"][0][1] == max(g for _, g in b["idle_gaps"])
    assert tr.breakdown([], end)["idle_gaps"][0][0] == "no phase"


def test_readers_on_the_recorded_trace(tr):
    end = max(e for _, _, e in tr.ops)
    run = types.SimpleNamespace(device=tr, window_s=end, spans=[])
    per_step = harness.reader("bsr_ms_per_step")(run)
    assert per_step == pytest.approx(
        1e3 * tr.op_time(devtrace.BSR_KERNEL, within=devtrace.DECODE_PROGRAM))
    idle = harness.reader("device_idle_share")(run)
    assert 0 < idle < 100
    assert harness.reader("decode_step_ms")(run) == pytest.approx(39.757958)
    assert harness.reader("prefill_ms")(run) > 0
    # nothing traced: the readers return nothing rather than a zero
    none = types.SimpleNamespace(device=None, spans=None, window_s=1.0)
    for m in ("bsr_ms_per_step", "device_idle_share", "decode_step_ms",
              "bsr_roofline", "kv_gather_ms"):
        assert harness.reader(m)(none) is None


def test_renamed_kernel_is_an_error(tr):
    """Steps that ran with no event under the kernel's name stop the run
    rather than leave the kernel's metrics silent."""
    renamed = devtrace.DeviceTrace(tr.programs, [
        ("bsr_matmul_renamed" if n == devtrace.BSR_KERNEL else n, s, e)
        for n, s, e in tr.ops])
    end = max(e for _, _, e in tr.ops)
    run = types.SimpleNamespace(device=renamed, window_s=end, spans=[])
    for m in ("bsr_ms_per_step", "bsr_roofline"):
        with pytest.raises(ValueError, match="bsr_matmul_stacked"):
            harness.reader(m)(run)
