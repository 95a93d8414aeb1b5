"""The harness against its own contract: names resolve, traffic is a pure
function of the seed and survives the server's tokenizer, the parent stays
off JAX, and a rehearsal prints the result line."""

import importlib
import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import cellfiles
from benchmarks.generators import closed_loop, open_loop, prompts

REPO = cellfiles.REPO
BENCH = cellfiles.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_has_the_contracts_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"] and 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for g in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[g]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert all(len(x["why"]) <= 200 for x in BENCH["configs"] + BENCH["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert {c["name"] for c in BENCH["configs"]} == {w["config"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] == 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace") for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        mine = set(m.get("workloads", CELLS))
        assert mine <= set(moved.get("workloads", CELLS)), m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_every_name_of_a_cell_resolves_to_a_file(name):
    cell = cellfiles.Cell(name)
    importlib.import_module(f"benchmarks.generators.{cell.generator}")
    assert (REPO / "benchmarks" / "children" / f"{cell.kind}.py").exists()
    importlib.import_module(f"benchmarks.reference.{cell.family['reference']}")
    for group in ("end_to_end", "per_layer"):
        metrics = cell.metrics(group)
        assert metrics, f"{name} reports no {group} metric"
        for m in metrics:
            spec = cellfiles.metric_file(group, m["name"])
            reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
            assert callable(reader.read)
            if "opsbytes" in spec["args"]:
                module = importlib.import_module(
                    f"benchmarks.opsbytes.{spec['args']['opsbytes']}")
                assert callable(module.needed)
    assert {m["name"] for m in cell.metrics("end_to_end")} > {"setup_s"}
    for key in ("source", "reduced", "assumed", "family"):
        assert key in cell.model
    for key in ("kind", "chips", "reduced", "why_reduced", "stands_for"):
        assert key in cell.cut


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configuration_file_holds_what_its_named_cut_runs(entry):
    model = cellfiles.load_json(REPO / entry["file"])
    assert model["source"] == entry["source"] and model["reduced"] == entry["reduced"]
    cut = cellfiles.load_json(
        (REPO / entry["file"]).parent / "cuts" / f"{model['as_run_cut']}.json")
    for key in entry["reduced"]:
        assert model[key] == cut["reduced"][key] != model["published"][key]
    widths = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$"
                        r"|head_dim|expansion|experts_per_tok")
    assert not any(widths.search(k) for k in entry["reduced"])
    # every cut of it changes listed keys only
    for path in ((REPO / entry["file"]).parent / "cuts").glob("*.json"):
        assert set(cellfiles.load_json(path)["reduced"]) <= set(entry["reduced"])


def serving_cells():
    return [n for n in CELLS if cellfiles.Cell(n).kind == "serve"]


def requests_of(cell, seed, seconds=20.0):
    limits = prompts.Limits.of(cell)
    if cell.generator == "open_loop":
        return open_loop.schedule(cell.mix, cell.load["rate_rps"], seed, seconds, limits), limits
    return [{**closed_loop.caller_request(cell.mix, seed, c, k, limits), "caller": c}
            for c in range(3) for k in range(8)], limits


@pytest.mark.parametrize("name", serving_cells())
def test_traffic_is_a_pure_function_of_the_seed(name):
    cell = cellfiles.Cell(name)
    a, _ = requests_of(cell, 7)
    b, _ = requests_of(cell, 7)
    c, _ = requests_of(cell, 8)
    assert a == b and a != c
    if cell.generator == "open_loop":
        # The trace is the mix's: another seed changes what requests say only.
        shape = lambda rs: [(r["due"], r["session"], r["turn"], r["prompt_tokens"],
                             r["max_tokens"]) for r in rs]
        assert shape(a) == shape(c)
        assert all(x["content"] != y["content"] for x, y in zip(a, c))
        assert all(x["due"] <= y["due"] for x, y in zip(a, a[1:]))
        started = [r for r in a if r["turn"] == 0 and r["due"] >= 0]
        assert len(started) == round(
            cell.load["rate_rps"] / cell.mix["sessions"]["requests"] * 20.0)
        other = open_loop.schedule({**cell.mix, "trace_seed": 1}, cell.load["rate_rps"],
                                   7, 20.0, prompts.Limits.of(cell))
        assert shape(other) != shape(a)
        if cell.mix["sessions"]["requests"] == 1:
            for i in (3, 4):   # prompt and output lengths: one multiset for every trace
                assert sorted(x[i] for x in shape(other) if x[0] >= 0) == \
                    sorted(x[i] for x in shape(a) if x[0] >= 0)


@pytest.fixture(scope="module")
def server_encode():
    """The server's own `Engine.encode`, unbound, on a stand-in for `self`."""
    from benchmarks.children import serve

    server = serve.load_server_module()

    def encode(text, limits):
        stand_in = SimpleNamespace(
            config=SimpleNamespace(vocab_size=limits.vocab_size,
                                   max_seq_len=limits.max_seq_len),
            max_new_tokens=limits.max_new_tokens, MIN_BUCKET=server.Engine.MIN_BUCKET)
        return server.Engine.encode(stand_in, text)[0]

    return encode


@pytest.mark.parametrize("name", serving_cells())
def test_prompts_encode_to_exactly_their_bucket(name, server_encode):
    cell = cellfiles.Cell(name)
    requests, limits = requests_of(cell, 3)
    assert requests
    for r in requests:
        text = prompts.render(r["content"])
        ids = prompts.encode(text, limits)
        assert ids == server_encode(text, limits), "the copy left the server's rule"
        assert len(ids) == r["prompt_tokens"]
        assert bytes(ids).decode() == text, "the tokenizer cut or padded the prompt"
        assert 1 <= r["max_tokens"] <= limits.max_new_tokens
        assert r["prompt_tokens"] + r["max_tokens"] <= limits.max_seq_len
    # A length that is not a bucket is what the builder exists to avoid.
    off = prompts.render("x" * 100)
    assert len(prompts.encode(off, limits)) == 64 != len(off.encode())


def test_docqa_shared_head_survives_encoding(server_encode):
    cell = cellfiles.Cell("mistral-7b.docqa")
    requests, limits = requests_of(cell, 5)
    head = cell.mix["prompt"]["shared_head_tokens"]
    per_session = cell.mix["sessions"]["requests"]
    sessions = {}
    for r in requests:
        sessions.setdefault((r["caller"], r["session"]), []).append(
            server_encode(prompts.render(r["content"]), limits))
    assert len(sessions) == 6 and all(len(v) == per_session for v in sessions.values())
    for encoded in sessions.values():
        assert all(e[:head] == encoded[0][:head] for e in encoded)
        tails = {tuple(e[head:]) for e in encoded}
        assert len(tails) == len(encoded), "questions of one session must differ"
    first_heads = {tuple(v[0][:head]) for v in sessions.values()}
    assert len(first_heads) == len(sessions), "documents of two sessions must differ"
    assert head % 16 == 0  # whole KV blocks, so the prefix cache can hold all of it
    # The shape of the traffic is the mix's; the seed changes what is said.
    other, _ = requests_of(cell, 6)
    assert [(r["prompt_tokens"], r["max_tokens"]) for r in other] == \
        [(r["prompt_tokens"], r["max_tokens"]) for r in requests]
    assert len({r["max_tokens"] for r in requests}) > 4


def test_stratified_lengths_are_a_fixed_multiset():
    import random

    chat = cellfiles.load_json(cellfiles.BENCH_DIR / "traffic" / "chat.json")
    a = prompts.stratified(chat["prompt"]["total_tokens"], 100, random.Random(1))
    b = prompts.stratified(chat["prompt"]["total_tokens"], 100, random.Random(2))
    assert sorted(a) == sorted(b) and a != b
    assert [a.count(v) for v in (64, 128, 256, 512, 1024, 2048)] == [15, 25, 25, 20, 10, 5]
    out = prompts.stratified(chat["output_tokens"], 1000, random.Random(1))
    assert min(out) >= 16 and max(out) <= 512
    assert sorted(out)[500] in range(124, 133)          # median 128
    assert 150 < sum(out) / len(out) < 170              # mean of the clipped lognormal


def test_the_parent_never_imports_jax():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import benchmarks.run, benchmarks.sweep, benchmarks.cellfiles, benchmarks.childproc\n"
        "import benchmarks.trace_reduce\n"
        "import importlib, pkgutil, benchmarks.generators, benchmarks.readers, benchmarks.opsbytes\n"
        "for pkg in (benchmarks.generators, benchmarks.readers, benchmarks.opsbytes):\n"
        "    for m in pkgutil.iter_modules(pkg.__path__):\n"
        "        importlib.import_module(pkg.__name__ + '.' + m.name)\n"
        "assert 'jax' not in sys.modules, 'a parent-side module imports jax'\n"
    ) % str(REPO)
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO)


def test_no_branch_on_a_cell_or_configuration_name():
    names = CELLS + [c["name"] for c in BENCH["configs"]] + \
        [w["traffic"] for w in BENCH["workloads"]]
    for path in (REPO / "benchmarks").rglob("*.py"):
        if "tests" in path.parts:
            continue
        code = "\n".join(line.split("#")[0] for line in path.read_text().splitlines()
                         if not line.lstrip().startswith(('"', "'")))
        for n in names:
            assert not re.search(r"(==|!=|\bin\b)\s*\(?\s*[\"']%s[\"']" % re.escape(n), code), \
                f"{path.name} compares against the name {n!r}"


@pytest.mark.parametrize("name,trace", [("mistral-7b.chat", 0), ("mistral-7b.docqa", 1),
                                         ("mixtral-8x7b.train", 0)])
def test_a_rehearsal_prints_the_contracts_line(name, trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", name, "--seed", "4",
         "--seconds", "4", "--trace", str(trace), "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device", "rehearsal"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in cellfiles.Cell(name).metrics(group)}
    assert line["metrics"] and set(line["metrics"]) <= allowed
    for name_, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float), name_
    if not trace:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's paths."""
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral-7b.chat", "--seed", "1",
         "--seconds", "2", "--trace", "0", "--rehearsal"],
        cwd=tmp_path, env={**env, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_percentiles_are_harrell_davis_estimates():
    import random

    from benchmarks.readers import client

    assert client.beta_cdf(0.5, 2, 2) == pytest.approx(0.5)
    assert client.beta_cdf(0.25, 1, 1) == pytest.approx(0.25)
    assert client.percentile([7.0], 90) == 7.0
    assert client.percentile([1, 2, 3, 4, 5], 50) == pytest.approx(3.0)
    rng = random.Random(0)
    sample = [rng.gauss(0, 1) for _ in range(4000)]
    assert client.percentile(sample, 90) == pytest.approx(1.2816, abs=0.06)
    # An estimate of the same quantile as the order statistic, not a mean of the tail:
    tail = sorted(sample)[-400:]
    assert client.percentile(sample, 90) < sum(tail) / len(tail) - 0.3
    # and one moved request moves it by a fraction of what it moves its own rank.
    v = [float(i) for i in range(41)]
    moved = v[:36] + [v[36] + 1.0] + v[37:]
    assert 0 < client.percentile(moved, 90) - client.percentile(v, 90) < 0.3
