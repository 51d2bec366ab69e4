"""The readers the outage cell adds: the BCH decode's device time, the
binning roofline at n = 511, and the hub's reply loop.  Each reads its
source per reconciliation, and reports nothing where the run holds no
trace or spans, or the program has none of that name."""
from pathlib import Path

import pytest

import run
import trace_reduce
from work import bin_xorsum

PEAKS = {"hbm_byte_per_s": 819e9}
DATA = Path(__file__).resolve().parent / "data"


def _op_s():
    op_s = {}
    for _, name, _, d in trace_reduce.load_json(DATA / "handoff_ops.json").device:
        op_s[name] = op_s.get(name, 0.0) + d / 1e9
    return op_s


def _run(spans=None, op_s=None, work=None, recons=4):
    trace = None if op_s is None else {"op_s": op_s}
    return run.Run(steps=[], recons=[object()] * recons, window_s=60.0,
                   setup_s=40.0, correct_recons=recons, spans=spans,
                   device_trace=trace, work=work, peaks=PEAKS)


def test_decode_time_takes_the_decode_programs_alone():
    op_s = _op_s()
    decode = [n for n in op_s if n.startswith("jit_bch_decode_batched/")]
    assert decode and len(decode) < len(op_s)
    got = run.metric_reader("decode_ms_per_recon")(_run(op_s=op_s))
    assert got == pytest.approx(sum(op_s[n] for n in decode) * 1e3 / 4)
    # a decode fused into another program is not the decode program's time
    inline = {"jit__execute_round/bch_decode_batched_while.3": 1.0}
    assert run.metric_reader("decode_ms_per_recon")(_run(op_s=inline)) is None


def test_binning_roofline_at_n511_reads_like_the_handoff_one():
    op_s = _op_s()
    work = [(65_536, 1_000_000, 1_000_000, 511), (100, 4_000, 4_000, 511)]
    got = run.metric_reader("bin_xorsum_roofline_n511")(_run(op_s=op_s, work=work))
    same = run.metric_reader("bin_xorsum_roofline")(_run(op_s=op_s, work=work))
    assert got == pytest.approx(same)
    assert got == pytest.approx(100 * bin_xorsum.least_seconds(work, PEAKS)
                                / bin_xorsum.device_seconds(op_s))


def test_reply_loop_sums_its_spans_per_reconciliation():
    spans = [("decode.reply_units", 0.03), ("decode.reply_units", 0.01),
             ("device.readback", 0.5), ("hub.decode", 0.2)]
    got = run.metric_reader("reply_units_ms_per_recon")(_run(spans=spans))
    assert got == pytest.approx(40.0 / 4)


@pytest.mark.parametrize("name", ["decode_ms_per_recon", "bin_xorsum_roofline_n511",
                                  "reply_units_ms_per_recon"])
def test_untraced_or_older_program_reports_nothing(name):
    """Untraced, or a program older than the span (the parent of the change
    that added it), leaves the metric out of the result line."""
    read = run.metric_reader(name)
    assert read(_run()) is None
    older = [("hub.decode", 0.2), ("device.readback", 0.5)]
    no_ops = {"jit__encode_side/fusion.2": 1.0}
    assert read(_run(spans=older, op_s=no_ops, work=[(1, 10, 10, 511)])) is None
