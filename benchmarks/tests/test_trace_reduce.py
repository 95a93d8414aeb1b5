"""trace_reduce.py on traces with known answers, so that every later PR
computes busy and idle time, per-operation sums and gap names the same way.

synthetic.xplane.txt is hand-made in the profiler's own text format: every
expected number below can be read off its header. small.xplane.pb is
recorded on the v5e (see testdata/README.md); it pins what a real trace
looks like (plane and line names, module names, where a kernel's name is)."""

from pathlib import Path

import pytest

from benchmarks import trace_reduce

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    from jax.profiler import ProfileData

    text = "\n".join(
        line for line in (TESTDATA / "synthetic.xplane.txt").read_text().splitlines()
        if not line.startswith("#")
    )
    path = tmp_path_factory.mktemp("trace") / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.reduce(path)


def test_busy_is_the_union_of_operation_intervals(synthetic):
    # device 0: [1000, 6000) + [10000, 12000) + [20000, 24000) = 11000 ns,
    # the nested fusion and kernel inside the while counted once.
    # device 1: 3000 ns. Mean 7000 ns. Window: first to last DEVICE event,
    # 1000..25000; the host's events before and after do not stretch it.
    assert synthetic["devices"] == 2
    assert [d["busy_s"] for d in synthetic["per_device"]] == pytest.approx([11e-6, 3e-6])
    assert synthetic["busy_s"] == pytest.approx(7e-6)
    assert synthetic["window_s"] == pytest.approx(24e-6)


def test_modules_are_named_without_their_fingerprint(synthetic):
    assert set(synthetic["modules"]) == {"jit_decode_steps", "jit_chunk_prefill"}
    decode = synthetic["modules"]["jit_decode_steps"]
    assert decode["count"] == 3
    assert decode["durations_s"] == pytest.approx([3e-6, 5e-6, 5e-6])


def test_operation_sums_are_self_time_inside_their_module(synthetic):
    decode = synthetic["ops"]["jit_decode_steps"]
    # while.1 lasts 4000 ns and holds 1000 + 1500 ns of nested operations.
    assert decode["while.1"]["self_s"] == pytest.approx(1.5e-6)
    assert decode["custom-call.3"] == {
        "count": 2, "self_s": pytest.approx(5.5e-6),
        "detail": "tf_op=jit(decode_steps)/while/body/ragged_paged_attention",
    }
    assert synthetic["ops"]["jit_chunk_prefill"]["fusion.9"]["count"] == 1
    # Self times over both devices add up to the busy time over both.
    total = sum(row["self_s"] for rows in synthetic["ops"].values() for row in rows.values())
    assert total == pytest.approx(14e-6)


def test_a_kernel_is_found_by_the_name_in_its_detail(synthetic):
    found = trace_reduce.matching_ops(synthetic, "ragged_paged_attention", "decode_steps")
    # per device: 2 calls and 5500 ns over 2 devices
    assert found == {"count": 1.0, "self_s": pytest.approx(2.75e-6)}


def test_an_unmatched_pattern_raises(synthetic):
    with pytest.raises(trace_reduce.TraceError, match="matches nothing"):
        trace_reduce.matching_ops(synthetic, "flash_fwd")
    with pytest.raises(trace_reduce.TraceError, match="jit_chunk_prefill"):
        trace_reduce.matching_modules(synthetic, "train_step")
    with pytest.raises(trace_reduce.TraceError, match="matches nothing"):
        trace_reduce.matching_ops(synthetic, "fusion.9", "decode_steps")


def test_gaps_go_to_the_host_event_that_fits_them(synthetic, monkeypatch):
    # No module on device 0 in [0, 1000), [6000, 10000), [12000, 20000),
    # [25000, 30000): 18000 ns. _fan_out covers [6000, 10000) exactly;
    # _advance_prefills fits [12000, 20000) better than the 30000 ns select,
    # which takes the two stretches nothing else overlaps.
    monkeypatch.setattr(trace_reduce, "MIN_GAP_NS", 500)
    gaps = dict(trace_reduce.name_gaps(
        trace_reduce.gaps_between([(1000, 6000), (10000, 12000), (20000, 25000)], 0, 30000),
        [(6000, 10000, "_fan_out", ""), (12500, 19000, "_advance_prefills", ""),
         (0, 30000, "select", "")],
    ))
    assert gaps == pytest.approx(
        {"_fan_out": 4e-6, "_advance_prefills": 8e-6, "select": 6e-6})
    named = dict(synthetic["breakdown"]["idle_gaps"])
    assert synthetic["gap_s"] == pytest.approx(sum(named.values()))
    assert len(synthetic["breakdown"]["device_ops"]) <= trace_reduce.TOP


def test_a_trace_without_a_device_is_refused(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "host_only.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { name: "/host:CPU" }'))
    with pytest.raises(trace_reduce.TraceError, match="no device plane"):
        trace_reduce.reduce(path)


RECORDED = TESTDATA / "small.xplane.pb"


def test_recorded_trace_from_the_chip():
    # 276 ms of mistral-7b.chat on the v5e: one chunk-prefill program, then
    # one decode program of 4 steps x 12 layers (testdata/README.md).
    reduced = trace_reduce.reduce(RECORDED)
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.275636, abs=1e-6)
    assert reduced["busy_s"] == pytest.approx(0.254097, abs=1e-6)
    assert reduced["gap_s"] == pytest.approx(0.021513, abs=1e-6)
    assert reduced["modules"]["jit_decode_steps"]["count"] == 1
    assert reduced["modules"]["jit_decode_steps"]["total_s"] == pytest.approx(0.201728, abs=1e-6)
    assert reduced["modules"]["jit_chunk_prefill"]["count"] == 1
    found = trace_reduce.matching_ops(reduced, "ragged_paged_attention", "decode_steps")
    assert found == {"count": 48.0, "self_s": pytest.approx(0.036304, abs=1e-6)}
    # On the chip an operation is named by its whole HLO line; the breakdown
    # keeps the name and what it produces.
    top = reduced["breakdown"]["device_ops"][0][0]
    assert top == "jit_decode_steps: %ragged_paged_attention.12 bf16[16,32,128]"
    assert any("_fan_out" in name for name, _ in reduced["breakdown"]["idle_gaps"])
