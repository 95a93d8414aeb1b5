"""span_reduce.py on a hand-made trace: every expected number below can be
read off the header of testdata/spans.xplane.txt (times there are in us)."""

from pathlib import Path

import pytest

from benchmarks import span_reduce, trace_reduce
from benchmarks.readers import spans as spans_reader

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
US = 1e-6


def write_xplane(text_file: str, directory: Path) -> Path:
    from jax.profiler import ProfileData

    text = "\n".join(line for line in (TESTDATA / text_file).read_text().splitlines()
                     if not line.startswith("#"))
    path = directory / text_file.replace(".txt", ".pb")
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    return write_xplane("spans.xplane.txt", tmp_path_factory.mktemp("spans"))


@pytest.fixture(scope="module")
def reduced(xplane):
    return span_reduce.reduce(xplane)


def test_gaps_and_stretch_are_trace_reduces(reduced, xplane):
    theirs = trace_reduce.reduce(xplane)
    assert reduced["window_s"] == pytest.approx(2390 * US)
    assert reduced["gap_s"] == pytest.approx(870 * US)
    assert reduced["gaps"] == 3  # [1000, 1010) is under MIN_GAP_NS
    assert reduced["window_s"] == pytest.approx(theirs["window_s"])
    assert reduced["gap_s"] == pytest.approx(theirs["gap_s"])


def test_every_gap_goes_to_the_deepest_span_over_it(reduced):
    want = {"(unattributed)": 220, "sync": 40, "barrier": 50, "fan_out": 210,
            "wait": 100, "admit": 65, "admit/match": 20, "admit/chunk_args": 50,
            "admit/chunk_launch": 10, "grow": 10, "dispatch": 90, "cycle": 5}
    assert reduced["idle_in_s"] == pytest.approx({k: v * US for k, v in want.items()})
    assert sum(reduced["idle_in_s"].values()) == pytest.approx(reduced["gap_s"])


def test_only_the_loops_spans_are_read(reduced):
    # Neither the `_fan_out` frame on the loop's line nor the HTTP thread.
    assert reduced["spans"] == {
        "cycle": 2, "admit": 2, "admit/match": 1, "admit/chunk_args": 1,
        "admit/chunk_launch": 1, "grow": 2, "dispatch": 2, "sync": 3,
        "barrier": 3, "fan_out": 2, "wait": 1}


def test_a_phase_takes_what_is_nested_in_it(reduced):
    assert span_reduce.idle_in(reduced, "admit") == pytest.approx(145 * US)
    assert span_reduce.idle_in(reduced, "admit/chunk_args") == pytest.approx(50 * US)
    assert span_reduce.idle_in(reduced, "(unattributed)") == pytest.approx(220 * US)
    assert span_reduce.idle_in(reduced, "prefill") == 0.0


def test_the_reader_runs_the_reduction_once_and_keeps_it(xplane, tmp_path):
    out_dir = tmp_path / "cell"
    trace_dir = out_dir / "trace" / "plugins" / "profile" / "stamp"
    trace_dir.mkdir(parents=True)
    copy = trace_dir / "host.xplane.pb"
    copy.write_bytes(xplane.read_bytes())
    obs = {"kind": "serve", "trace": {"xplane": str(copy)}}
    args = {"op": "idle_in", "phase": "fan_out"}
    assert spans_reader.read(obs, args) == pytest.approx(100 * 210 / 2390)
    assert (out_dir / "spans_reduced.json").exists()
    copy.unlink()  # a second metric reads what the first one left in obs
    assert spans_reader.read(obs, {"op": "idle_in", "phase": "admit"}) == \
        pytest.approx(100 * 145 / 2390)
    shares = [spans_reader.read(obs, {"op": "idle_in", "phase": p}) for p in (
        "admit", "grow", "dispatch", "sync", "barrier", "fan_out", "(unattributed)")]
    # The seven reported shares leave out `wait` and a bare `cycle`.
    assert sum(shares) == pytest.approx(100 * (870 - 100 - 5) / 2390)


def test_no_trace_reads_as_nothing():
    assert spans_reader.read({"kind": "serve"}, {"op": "idle_in", "phase": "admit"}) is None
    assert spans_reader.read({"kind": "serve", "trace": None},
                             {"op": "idle_in", "phase": "admit"}) is None


def test_a_trace_without_engine_spans_is_unread(tmp_path):
    """A program from before the phase clock: the device idles, nothing marks
    the loop. Never a zero."""
    path = write_xplane("synthetic.xplane.txt", tmp_path)
    with pytest.raises(trace_reduce.TraceError, match="engine/"):
        span_reduce.reduce(path)
    obs = {"kind": "serve", "trace": {"xplane": str(path)}}
    for _ in range(2):  # the failure is kept too: one subprocess, not seven
        with pytest.raises(trace_reduce.TraceError, match="engine/"):
            spans_reader.read(obs, {"op": "idle_in", "phase": "sync"})
    assert "error" in obs["spans"]


def test_nested_spans_become_disjoint_segments():
    spans = [(0, 100, "cycle"), (0, 40, "admit"), (10, 20, "admit/match"),
             (40, 101, "sync"), (150, 160, "wait")]
    assert span_reduce.deepest_segments(spans) == [
        (0, 10, "admit"), (10, 20, "admit/match"), (20, 40, "admit"),
        (40, 100, "sync"),  # clipped to its parent
        (150, 160, "wait")]
