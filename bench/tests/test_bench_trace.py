"""The trace reduction on hand-built event lists."""
import pytest

from bench.lib import trace as T


def ev(name, start, dur, text=""):
    return T.Event(name, start, dur, text)


def hand_trace():
    dev = [ev("while.4", 0.0, 1.5),
           ev("fusion.1", 0.0, 1.0),
           ev("lora_matmul.2", 0.5, 1.0),
           ev("fusion.7", 3.0, 2.0),
           ev("quant_matmul_t.3", 6.0, 1.0),
           ev("fusion.1", 9.5, 2.0)]
    host = [ev("bench.window", 0.0, 10.0),
            ev("bench.round", 1.0, 3.0),
            ev("bench.block", 1.2, 1.6),
            ev("bench.round", 5.0, 0.5)]
    return T.Trace(devices=[dev], host=host, t0=0.0, t1=10.0)


def test_union_merges_and_clips():
    assert T.union([(0, 1), (0.5, 2), (3, 4), (9, 12)], 0, 10) == \
        [(0, 2), (3, 4), (9, 10)]


def test_busy_and_idle_share():
    tr = hand_trace()
    # busy: [0, 1.5] + [3, 5] + [6, 7] + [9.5, 10] = 1.5 + 2 + 1 + 0.5
    assert T.busy_s(tr) == pytest.approx(5.0)
    assert T.idle_share(tr) == pytest.approx(50.0)


def test_busy_averages_devices():
    tr = hand_trace()
    tr.devices.append([ev("fusion.9", 0.0, 10.0)])
    assert T.busy_s(tr) == pytest.approx(7.5)


def test_short_name_of_tpu_op_text():
    text = ("%lora_matmul.118 = bf16[5120,3072]{1,0} custom-call("
            "bf16[5120,768]{1,0} %pad.275), custom_call_target=\"x\"")
    e = T.Event(T.short_name(text), 0.0, 1.0, text)
    assert e.name == "lora_matmul.118" and e.base == "lora_matmul"
    assert T.Event("quant_matmul_t.66", 0, 1).base == "quant_matmul_t"
    assert T.Event("copy-start", 0, 1).base == "copy-start"


def test_kernel_time_by_name_inside_window():
    tr = hand_trace()
    assert T.kernel_time(tr, ["lora_matmul"]) == (1, pytest.approx(1.0))
    assert T.kernel_time(tr, ["quant_matmul_t", "lora_matmul"]) == \
        (2, pytest.approx(2.0))
    # the last fusion overruns the window's end: not a whole event in it
    assert T.kernel_time(tr, ["fusion"]) == (2, pytest.approx(3.0))
    assert T.kernel_time(tr, ["lora"]) == (0, 0.0)


def test_top_ops_clipped_to_window():
    ops = dict((n, s) for n, s in T.top_ops(hand_trace()))
    assert ops["fusion"] == pytest.approx(1.0 + 2.0 + 0.5)
    assert ops["lora_matmul"] == pytest.approx(1.0)
    assert "while" not in ops


def test_idle_gaps_by_host_span():
    gaps = dict((n, s) for n, s in T.idle_gaps(hand_trace()))
    # gaps: [1.5, 3] mid 2.25 in bench.block; [5, 6] mid 5.5 at the
    # round's end (5.0 + 0.5, inclusive); [7, 9.5] mid 8.25 in no span
    assert gaps["bench.block"] == pytest.approx(1.5)
    assert gaps["bench.round"] == pytest.approx(1.0)
    assert gaps["(no span)"] == pytest.approx(2.5)
    assert sum(gaps.values()) == pytest.approx(5.0)


def test_breakdown_lists_at_most_ten():
    tr = hand_trace()
    tr.devices[0] += [ev(f"op{i}.1", 7.0 + 0.1 * i, 0.05)
                      for i in range(20)]
    b = T.breakdown(tr)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10


def test_readers_on_hand_trace():
    from bench.lib import flops, harness, readers
    pk = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    calls = [{"flops": 2e11, "bytes": 1e9}, {"flops": 1e11, "bytes": 4e10}]
    x = harness.Reading(trace=hand_trace(), peaks=pk, config={},
                        traffic={}, layer={
                            "work": 4, "flops_per_work": 1e12,
                            "kernels": {"lora_matmul": calls}})
    # least times 0.2 s and 0.4 s: mean 0.3 s a call; one call, 1 s
    assert flops.mean_least_time(calls, pk) == pytest.approx(0.3)
    assert readers.roofline(x, "lora_matmul", ["lora_matmul"]) == \
        pytest.approx(30.0)
    assert readers.roofline(x, "lora_matmul", ["nothing"]) is None
    assert readers.roofline(x, "absent", ["lora_matmul"]) is None
    # 4 units of 1 TFLOP over the 10 s window at 1 TFLOP/s
    assert readers.mfu(x) == pytest.approx(40.0)
    assert readers.idle(x) == pytest.approx(50.0)
    empty = harness.Reading(trace=None, peaks=pk, config={}, traffic={},
                            layer={})
    assert readers.idle(empty) is None and readers.mfu(empty) is None
