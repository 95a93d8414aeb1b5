"""Every part of the paged programs carries a `jax.named_scope`, and
`benchmarks/scope_reduce.py` resolves a device operation to it through the
optimised HLO a profiler trace holds (PERF.md section 3, "programs").

Four things are held here, all on the CPU:

1. the reader's protobuf wire decoding, against JAX's own text of a compiled
   module and against a trace JAX's profiler wrote in this test;
2. coverage: in the optimised decode and chunk-prefill programs of every tiny
   preset, an instruction that is or holds a matrix product resolves to a
   component of the model (a new model part without a scope fails here);
3. the scopes are names only: with `jax.named_scope` patched to a no-op the
   optimised HLO is the same text apart from `metadata={...}`;
4. the reduction on a hand-made trace with known answers.

The persistent compile cache is off for the whole file: its key ignores
metadata, so a program compiled before a scope was added comes back from it
with the OLD names (PERF.md section 7).
"""

import contextlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks import scope_reduce
from benchmarks.readers import scopes as scopes_reader
from benchmarks.trace_reduce import TraceError, find_xplane
from dstack_tpu.workloads import kv_blocks
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.transformer import init_params

PRESET_NAMES = ("tiny", "tiny-moe", "tiny-latent", "tiny-window", "tiny-mamba",
                "tiny-laguna", "tiny-zaya")
PROGRAM_NAMES = ("decode_steps", "chunk_prefill")
SLOTS, CHUNK, BLOCK, STEPS = 4, 32, 16, 2


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


# ------------------------------------------------------ a protobuf writer
# (the test's own, a dozen lines: what `scope_reduce.fields` must read back)

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def hlo_proto_of(compiled) -> bytes:
    module = compiled.runtime_executable().hlo_modules()[0]
    return field(1, module.as_serialized_hlo_module_proto())


# ------------------------------------------------- 1. the wire reader

def two_scopes(x, w):
    with jax.named_scope("first"):
        h = jnp.tanh(x @ w)
    with jax.named_scope("second/inner"):
        return jax.nn.softmax(h @ w.T)


def test_wire_reader_agrees_with_the_compiled_text():
    x = jnp.ones((8, 16), jnp.float32)
    compiled = jax.jit(two_scopes).lower(x, jnp.ones((16, 16))).compile()
    by_name, bodies = scope_reduce.hlo_instructions(hlo_proto_of(compiled))
    want = {}
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = \S+ ([\w\-]+)\(", line)
        if m:
            op_name = re.search(r'op_name="([^"]*)"', line)
            want[m.group(1)] = (m.group(2), op_name.group(1) if op_name else "")
    assert len(want) > 5
    assert {n: (i.opcode, i.op_name) for n, i in by_name.items()} == want
    assert sum(len(names) for names in bodies.values()) == len(by_name)
    scopes = {i.op_name for i in by_name.values()}
    assert any("/first/" in s for s in scopes)
    assert any("/second/inner/" in s for s in scopes)
    # ... and a computation an instruction holds is found by its id.
    held = [i for i in by_name.values() if i.calls]
    assert all(c in bodies for i in held for c in i.calls)


def test_a_recorded_trace_holds_the_optimised_hlo_by_program(tmp_path):
    """The layout `/host:metadata` has in a trace JAX's profiler writes: one
    entry a program, named `jit_<name>(<n>)`, its one stat the `HloProto`."""
    fn = jax.jit(two_scopes)
    x, w = jnp.ones((8, 16), jnp.float32), jnp.ones((16, 16))
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn(x, w).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    raw = memoryview(find_xplane(tmp_path).read_bytes())
    programs = scope_reduce.metadata_programs(raw)
    mine = [k for k in programs if re.fullmatch(r"jit_two_scopes\(\d+\)", k)]
    assert len(mine) == 1, sorted(programs)
    by_name, _ = scope_reduce.hlo_instructions(programs[mine[0]])
    assert any(i.opcode == "dot" and "/first/" in i.op_name for i in by_name.values())
    assert "PROGRAM jit_two_scopes(" in scope_reduce.describe(tmp_path)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(decode_steps)/while/body/closed_call/while/body/closed_call/mlp/dot_general", "mlp"),
    ("jit(decode_steps)/while/body/closed_call/attn/full/while/body/add", "attn/full"),
    ("jit(chunk_prefill)/while/body/closed_call/attn/qkv/cond/branch_1_fun/mul", "attn/qkv"),
    ("jit(f)/moe/experts/moe/route/top_k", "moe/route"),           # the LAST one
    ("jit(step)/transpose(jvp(mlp))/dot_general", "mlp"),
    ("jit(step)/transpose(jvp(attn/qkv))/mul", "attn/qkv"),
    # a scope inside one of the vocabulary's counts with it (readers/scope_named.py
    # reads it apart)
    ("jit(decode_steps)/while/body/closed_call/attn/qkv/attn_gate/dot_general", "attn/qkv"),
    ("jit(decode_steps)/while/body/closed_call/attn/out/attn_gate/mul", "attn/out"),
    ("jit(decode_steps)/while/body/dynamic_slice", None),
    ("jit(headline)/attn/add", None),        # a part of a word is no scope
    ("", None),
])
def test_the_last_scope_of_the_vocabulary_names_an_operation(op_name, scope):
    assert scope_reduce.scope_of(op_name) == scope


def test_every_scope_maps_to_one_of_the_eight_components():
    assert set(scope_reduce.SCOPES.values()) | {"other"} == set(scope_reduce.COMPONENTS)
    assert len(scope_reduce.COMPONENTS) == 8


# ------------------------------------- 2. and 3. the programs' own scopes

def paged_program(name: str, cfg):
    """(jitted program, argument shapes) on the lax path, at a small geometry."""
    i32, f32 = jnp.int32, jnp.float32
    max_blocks = cfg.max_seq_len // BLOCK
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: kv_blocks.init_paged_state(
        cfg, SLOTS, cfg.max_seq_len, BLOCK, SLOTS * max_blocks))
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    if name == "decode_steps":
        fn = kv_blocks.make_paged_decode_step(cfg, STEPS, attn_impl="lax_ragged")
        return fn, (params, state, rng)
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype)
    fn = kv_blocks.make_chunk_prefill(cfg, CHUNK, attn_impl="lax_ragged")
    return fn, (
        params, state, scalar(i32), jax.ShapeDtypeStruct((max_blocks,), i32),
        jax.ShapeDtypeStruct((1, CHUNK), i32), scalar(i32), scalar(i32),
        scalar(i32), scalar(f32), scalar(f32), rng, scalar(jnp.bool_),
    )


def compile_programs(preset: str):
    out = {}
    for name in PROGRAM_NAMES:
        fn, args = paged_program(name, PRESETS[preset])
        out[name] = fn.lower(*args).compile()
    return out


@pytest.fixture(scope="module")
def compiled_programs():
    """Each preset's two programs, compiled once for the whole file."""
    kept = {}

    def get(preset):
        if preset not in kept:
            kept[preset] = compile_programs(preset)
        return kept[preset]

    return get


@pytest.mark.parametrize("name", PROGRAM_NAMES)
@pytest.mark.parametrize("preset", PRESET_NAMES)
def test_every_matrix_product_lies_under_a_component(preset, name, compiled_programs):
    program = scope_reduce.Program(hlo_proto_of(compiled_programs(preset)[name]))
    products, nameless = 0, []
    for instruction, row in program.by_name.items():
        if row.opcode in scope_reduce.HOLDS_A_BODY:
            continue
        holds = [row] + program.fused_into(instruction)
        if not any(i.opcode in scope_reduce.PRODUCTS for i in holds):
            continue
        if not any(i.op_name for i in holds):
            # XLA:CPU splits a product of several batch dimensions into one it
            # gives no metadata: nothing the program could have named.
            continue
        products += 1
        component, scope = program.component_of(instruction)
        if component == "other":
            nameless.append((instruction, row.opcode, [
                i.op_name for i in holds if i.opcode in scope_reduce.PRODUCTS]))
    assert products >= 4
    assert not nameless, nameless
    # ... and the model's parts are all there, under their own names.
    found = {program.component_of(n)[1] for n in program.by_name}
    want = {"embed", "head", "sample"}
    cfg = PRESETS[preset]
    want |= {"mla/project", "mla/attend"} if cfg.latent else {"attn/qkv", "attn/out"}
    want |= {"attn/write"}
    if cfg.n_experts:
        want |= {"moe/route", "moe/experts"}
    if not cfg.n_experts or cfg.n_dense_layers or cfg.has_state_layers:
        want |= {"mlp"}
    if cfg.has_state_layers:
        want |= {"mamba/proj", "mamba/conv", "mamba/scan", "mamba/state"}
    assert want <= found, sorted(want - found)


def test_the_head_gate_counts_with_the_projections_and_reads_apart(
        compiled_programs, monkeypatch):
    """The per-head output gate's product lies under `attn/qkv/attn_gate`:
    `attn_proj` to the component shares, and a component of its own name to
    the reduction `readers/scope_named.py` runs with the scope added."""
    proto = hlo_proto_of(compiled_programs("tiny-laguna")["decode_steps"])
    program = scope_reduce.Program(proto)
    gates = [n for n, row in program.by_name.items()
             if row.opcode in scope_reduce.PRODUCTS and "/attn_gate/" in row.op_name]
    assert gates and {program.component_of(n) for n in gates} == {("attn_proj", "attn/qkv")}
    monkeypatch.setattr(scope_reduce, "SCOPES", {**scope_reduce.SCOPES, "attn_gate": "attn_gate"})
    apart = scope_reduce.Program(proto)
    assert {apart.component_of(n) for n in gates} == {("attn_gate", "attn_gate")}
    others = [n for n, row in apart.by_name.items()
              if row.opcode in scope_reduce.PRODUCTS and "/attn/qkv/dot" in row.op_name]
    assert others and {apart.component_of(n)[0] for n in others} == {"attn_proj"}
    # a model without the gate has no such operation
    plain = scope_reduce.Program(hlo_proto_of(compiled_programs("tiny-window")["decode_steps"]))
    assert not [n for n, row in plain.by_name.items() if "attn_gate" in row.op_name]


def test_the_convolutions_count_with_the_attention_and_read_apart(compiled_programs, monkeypatch):
    """A CCA layer's convolutions over time, its QK-mean, norms, value shift
    and tail lie under `attn/full/attn_conv`: `attn` to the component shares,
    and a component of its own name to the reduction `readers/scope_named.py`
    runs with the scope added; its projections stay `attn_proj`."""
    proto = hlo_proto_of(compiled_programs("tiny-zaya")["decode_steps"])
    program = scope_reduce.Program(proto)
    convs = [n for n, row in program.by_name.items()
             if row.opcode in scope_reduce.PRODUCTS and "/attn_conv/" in row.op_name]
    assert convs and {program.component_of(n) for n in convs} == {("attn", "attn/full")}
    monkeypatch.setattr(scope_reduce, "SCOPES", {**scope_reduce.SCOPES, "attn_conv": "attn_conv"})
    apart = scope_reduce.Program(proto)
    assert {apart.component_of(n) for n in convs} == {("attn_conv", "attn_conv")}
    projections = [n for n, row in apart.by_name.items()
                   if row.opcode in scope_reduce.PRODUCTS and "/attn/qkv/" in row.op_name]
    assert projections and {apart.component_of(n)[0] for n in projections} == {"attn_proj"}


def strip_metadata(text: str) -> str:
    """The module's text without what only names things: each instruction's
    `metadata={...}` and the tables of files and stack frames it points into."""
    text = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:\d+ .*\n)+\n?", "", text, flags=re.M)
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    # ... and names by order of appearance: `%dot.48` counts the instructions
    # the lowering made before it, which a cached sub-function's changes.
    seen = {}
    return re.sub(
        r"%[\w.\-]+|\b[A-Za-z_][\w\-]*(?:\.\d+)+\b",
        lambda m: seen.setdefault(m.group(0).lstrip("%"), f"%{len(seen)}"), text)


@pytest.mark.parametrize("preset", PRESET_NAMES)
def test_scopes_are_names_only(preset, compiled_programs, monkeypatch):
    """The optimised HLO with `metadata={...}` stripped does not depend on the
    scopes: they cost nothing at run time and move no fusion."""
    scoped = compiled_programs(preset)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = compile_programs(preset)
    for name in PROGRAM_NAMES:
        by_name, _ = scope_reduce.hlo_instructions(hlo_proto_of(plain[name]))
        assert not [i.op_name for i in by_name.values()
                    if scope_reduce.scope_of(i.op_name)], "the patch took no scope away"
        assert strip_metadata(plain[name].as_text()) == \
            strip_metadata(scoped[name].as_text()), (preset, name)


# ------------------------------------------------ 4. a hand-made trace
#
# device 0, times in us. "XLA Modules":
#   A  jit_decode_steps(111)   [0, 1100)
#   B  jit_chunk_prefill(222)  [2000, 3000)
#   B2 jit_chunk_prefill(333)  [4000, 4500)    another bucket: its own fusion.1
#   C  jit_copy_block(444)     [5000, 5100)    no program in /host:metadata
# "XLA Ops":
#   in A:  while.1 [0, 900) holding fusion.1 [10, 410) (a dot under mlp),
#          ragged_paged_attention.3 [420, 620) (a kernel, no scope at all),
#          copy.5 [630, 700) (no op_name); then fusion.2 [900, 1000) (head/...),
#          mystery.1 [1000, 1050) (not an instruction of the program)
#     while.1 keeps 900 - 400 - 200 - 70 = 230 -> other
#     mlp 400, attn 200, head 100, other 230 + 70 + 50 = 350; total 1050
#   in B:  fusion.1 [2000, 2600) (own op_name under mlp, a dot under attn/qkv:
#          the product names it), gmm.7 [2600, 2900) (kernel), fusion.9
#          [2900, 3000) (no op_name; fused: moe/route twice, mlp once)
#     attn_proj 600, experts 300, router 100; total 1000
#   in B2: fusion.1 [4000, 4500) (own op_name under mamba/state) -> mixer 500
#   in C:  fusion.1 [5000, 5100): nothing resolves

def instruction(name, opcode, op_name="", calls=(), ident=0):
    body = field(1, name) + field(2, opcode) + field(35, ident)
    if op_name:
        body += field(7, field(2, op_name))
    if calls:
        body += field(38, b"".join(varint(c) for c in calls))   # packed
    return field(2, body)


def hlo_proto(name, computations):
    """computations: [(id, name, [instruction bytes])]"""
    module = field(1, name)
    for ident, comp_name, instructions in computations:
        module += field(3, field(1, comp_name) + b"".join(instructions) + field(5, ident))
    return field(1, module)


PROGRAM_A = hlo_proto("jit_decode_steps", [
    (1, "fused.1", [
        instruction("multiply.1", "multiply", "jit(decode_steps)/while/body/mlp/mul"),
        instruction("dot.1", "dot", "jit(decode_steps)/while/body/mlp/dot_general")]),
    (2, "body", [
        instruction("fusion.1", "fusion", "", calls=[1]),
        instruction("ragged_paged_attention.3", "custom-call"),
        instruction("copy.5", "copy")]),
    (3, "main", [
        instruction("while.1", "while", "jit(decode_steps)/mlp/while", calls=[2]),
        instruction("fusion.2", "fusion", "jit(decode_steps)/while/body/head/reduce_max")]),
])
PROGRAM_B = hlo_proto("jit_chunk_prefill", [
    (1, "fused.1", [
        instruction("dot.4", "dot", "jit(chunk_prefill)/while/body/attn/qkv/dot_general")]),
    (2, "fused.9", [
        instruction("add.1", "add", "jit(chunk_prefill)/while/body/moe/route/add"),
        instruction("add.2", "add", "jit(chunk_prefill)/while/body/moe/route/top_k"),
        instruction("add.3", "add", "jit(chunk_prefill)/while/body/mlp/add"),
        instruction("bitcast.1", "bitcast")]),
    (3, "main", [
        instruction("fusion.1", "fusion", "jit(chunk_prefill)/while/body/mlp/add", calls=[1]),
        instruction("gmm.7", "custom-call", "jit(chunk_prefill)/while/body/gmm"),
        instruction("fusion.9", "fusion", "", calls=[2])]),
])
PROGRAM_B2 = hlo_proto("jit_chunk_prefill", [
    (1, "main", [
        instruction("fusion.1", "fusion", "jit(chunk_prefill)/while/body/mamba/state/select_n")]),
])


def text_bytes(data: bytes) -> str:
    return "".join(f"\\{b:03o}" for b in data)


def hand_made_trace(with_metadata: bool = True) -> str:
    def events(rows):
        return " ".join(
            f"events {{ metadata_id: {m} offset_ps: {a}000000 duration_ps: {b - a}000000 }}"
            for m, a, b in rows)

    def metadata(rows):
        return "\n".join(
            f'  event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
            for k, n in rows)

    line = lambda op: f"%{op} = bf16[16,128]{{1,0}} {op.split('.')[0]}(%p)"
    device = f"""planes {{
  name: "/device:TPU:0"
  lines {{ name: "XLA Modules" timestamp_ns: 0
    {events([(1, 0, 1100), (2, 2000, 3000), (3, 4000, 4500), (4, 5000, 5100)])} }}
  lines {{ name: "XLA Ops" timestamp_ns: 0
    {events([(10, 0, 900), (11, 10, 410), (12, 420, 620), (13, 630, 700),
             (14, 900, 1000), (15, 1000, 1050),
             (11, 2000, 2600), (16, 2600, 2900), (17, 2900, 3000),
             (11, 4000, 4500), (11, 5000, 5100)])} }}
{metadata([(1, "jit_decode_steps(111)"), (2, "jit_chunk_prefill(222)"),
           (3, "jit_chunk_prefill(333)"), (4, "jit_copy_block(444)"),
           (10, line("while.1")), (11, line("fusion.1")),
           (12, line("ragged_paged_attention.3")), (13, line("copy.5")),
           (14, line("fusion.2")), (15, line("mystery.1")),
           (16, line("gmm.7")), (17, line("fusion.9"))])}
}}
"""
    if not with_metadata:
        return device
    programs = [(1, "jit_decode_steps(111)", PROGRAM_A),
                (2, "jit_chunk_prefill(222)", PROGRAM_B),
                (3, "jit_chunk_prefill(333)", PROGRAM_B2)]
    entries = "\n".join(
        f'  event_metadata {{ key: {k} value {{ id: {k} name: "{n}"'
        f' stats {{ metadata_id: 1 bytes_value: "{text_bytes(blob)}" }} }} }}'
        for k, n, blob in programs)
    return device + f"""planes {{
  name: "/host:metadata"
{entries}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
}}
"""


def write_trace(directory: Path, **kw) -> Path:
    from jax.profiler import ProfileData

    path = directory / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(hand_made_trace(**kw)))
    return path


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    return scope_reduce.reduce(write_trace(tmp_path_factory.mktemp("scopes")))


def ns(seconds: float) -> int:
    return round(seconds * 1e9)


def test_each_operation_goes_to_one_component(reduced):
    decode = reduced["modules"]["jit_decode_steps"]
    assert decode["programs"] == ["jit_decode_steps(111)"]
    assert {c: ns(s) for c, s in decode["by_component_s"].items() if s} == {
        "mlp": 400_000, "attn": 200_000, "head": 100_000, "other": 350_000}
    assert ns(decode["unresolved_s"]) == 50_000
    assert {s: ns(v) for s, v in decode["by_scope_s"].items()} == {
        "mlp": 400_000, "head": 100_000,
        "kernel:^(ragged|latent)_paged_attention": 200_000}
    # What no component owns, longest first: the loop's own time, the copy
    # without an op_name, the operation the program does not know.
    assert [(op, ns(s)) for op, s in decode["other_top"]] == [
        ("%while.1 bf16[16,128]", 230_000), ("%copy.5 bf16[16,128]", 70_000),
        ("%mystery.1 bf16[16,128]", 50_000)]


def test_two_programs_of_one_name_keep_their_own_instructions(reduced):
    """Both buckets own a `fusion.1`, under different scopes."""
    prefill = reduced["modules"]["jit_chunk_prefill"]
    assert prefill["programs"] == ["jit_chunk_prefill(222)", "jit_chunk_prefill(333)"]
    assert {c: ns(s) for c, s in prefill["by_component_s"].items() if s} == {
        "attn_proj": 600_000, "experts": 300_000, "router": 100_000, "mixer": 500_000}
    assert ns(prefill["unresolved_s"]) == 0


def test_components_sum_to_the_modules_total_to_the_nanosecond(reduced):
    assert set(reduced["modules"]) == {
        "jit_decode_steps", "jit_chunk_prefill", "jit_copy_block"}
    for module, total in (("jit_decode_steps", 1_050_000),
                          ("jit_chunk_prefill", 1_500_000), ("jit_copy_block", 100_000)):
        row = reduced["modules"][module]
        assert set(row["by_component_s"]) == set(scope_reduce.COMPONENTS)
        assert sum(ns(s) for s in row["by_component_s"].values()) == total
        assert ns(row["total_self_s"]) == total


def test_shares_are_of_the_programs_own_time(reduced):
    seconds, total = scope_reduce.component_seconds(
        reduced, "jit_chunk_prefill", ["mlp", "experts", "router"])
    assert (ns(seconds), ns(total)) == (400_000, 1_500_000)
    shares = [100 * scope_reduce.component_seconds(reduced, "jit_decode_steps", [c])[0]
              / 1050e-6 for c in scope_reduce.COMPONENTS]
    assert sum(shares) == pytest.approx(100.0)
    with pytest.raises(TraceError, match="matches nothing"):
        scope_reduce.component_seconds(reduced, "jit_spec_verify", ["attn"])
    # A program the trace holds no HLO of: not "all other".
    with pytest.raises(TraceError, match="no operation of"):
        scope_reduce.component_seconds(reduced, "jit_copy_block", ["other"])


def test_the_reader_runs_the_reduction_once_and_keeps_it(tmp_path):
    out_dir = tmp_path / "cell"
    trace_dir = out_dir / "trace" / "plugins" / "profile" / "stamp"
    trace_dir.mkdir(parents=True)
    copy = write_trace(trace_dir)
    obs = {"kind": "serve", "trace": {"xplane": str(copy)}}
    args = {"op": "component_share", "module": "jit_decode_steps", "components": ["mlp"]}
    assert scopes_reader.read(obs, args) == pytest.approx(100 * 400 / 1050)
    kept = json.loads((out_dir / "scopes_reduced.json").read_text())
    assert kept["modules"]["jit_decode_steps"]["programs"] == ["jit_decode_steps(111)"]
    copy.unlink()   # a second metric reads what the first one left in obs
    args = {"op": "component_share", "module": "jit_chunk_prefill",
            "components": ["mlp", "experts", "router"]}
    assert scopes_reader.read(obs, args) == pytest.approx(100 * 400 / 1500)


def test_no_trace_reads_as_nothing_and_no_hlo_as_unread(tmp_path):
    args = {"op": "component_share", "module": "jit_decode_steps", "components": ["attn"]}
    assert scopes_reader.read({"kind": "serve"}, args) is None
    assert scopes_reader.read({"kind": "serve", "trace": None}, args) is None
    path = write_trace(tmp_path, with_metadata=False)
    with pytest.raises(TraceError, match="/host:metadata"):
        scope_reduce.reduce(path)
    obs = {"kind": "serve", "trace": {"xplane": str(path)}}
    for _ in range(2):   # the failure is kept too: one subprocess
        with pytest.raises(TraceError, match="/host:metadata"):
            scopes_reader.read(obs, args)
    assert "error" in obs["scopes"]
