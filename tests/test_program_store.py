"""The program store (engine/program_store.py): a step program this tree,
configuration and device built before is loaded at its shape's first use,
not traced again; everything that decides a program names its entry; an
entry is never trusted.

On the CPU the store exists only where the engine's own configuration
places the cache (``compile_cache_dir``), so every test here places one in
a temporary directory and takes the session's variable out first.
"""

import dataclasses
import gc
import importlib.metadata
import os
import pickle
import shutil
import subprocess
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from production_stack_tpu.engine import program_store
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.program_store import (
    PLACEMENT_FIELDS,
    ProgramStore,
    StepPrograms,
    open_store,
    program_name,
    source_digest,
    wiring_digest,
)
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models.registry import get_model_config
from production_stack_tpu.obs import ENGINE_TELEMETRY, ENGINE_TELEMETRY_REGISTRY
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

from . import model_contract as contract

SESSION_CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR")

TINY = dict(
    model="tiny-llama-debug", max_model_len=64, block_size=16,
    num_kv_blocks=16, max_num_seqs=2, max_prefill_tokens=8,
    num_decode_steps=2, attn_impl="gather",
)

# jax's duration events while a test listens (one listener for the module:
# jax keeps no handle to take a lambda out again)
_events = []
_listening = []


def _on_duration(name, seconds, **kw):
    if _listening:
        _events.append(name)


monitoring.register_event_duration_secs_listener(_on_duration)


@pytest.fixture
def placed(tmp_path, monkeypatch):
    """A directory to place the cache in through ``compile_cache_dir``, with
    the session's variable (which would win) out of the way; afterwards
    jax's cache is back where the session keeps it."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        yield str(tmp_path)
    finally:
        from jax._src import compilation_cache

        jax.config.update("jax_compilation_cache_dir", SESSION_CACHE_DIR)
        compilation_cache.reset_cache()


def _outcomes() -> dict:
    return {o: ENGINE_TELEMETRY_REGISTRY.get_sample_value(
        "pst_engine_program_store_total", {"outcome": o})
        for o in ("loaded", "built", "rejected")}


def _first_use_seconds() -> float:
    return sum(s.value for m in ENGINE_TELEMETRY_REGISTRY.collect()
               if m.name == "pst_engine_program_first_use_seconds"
               for s in m.samples if s.name.endswith("_total"))


def _since(before: dict) -> dict:
    return {k: v - before[k] for k, v in _outcomes().items()}


def _drain(engine) -> dict:
    tokens = {}
    for _ in range(400):
        if not engine.has_work():
            return tokens
        for out in engine.step():
            tokens.setdefault(out.request_id, []).extend(out.new_token_ids)
    raise AssertionError("engine did not drain")


def _greedy(n):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


def _two_requests(engine) -> dict:
    engine.add_request("a", prompt_token_ids=list(range(2, 12)),
                       sampling=_greedy(5))
    engine.add_request("b", prompt_token_ids=[5, 6, 7], sampling=SamplingParams(
        max_tokens=4, temperature=0.8, seed=3, ignore_eos=True))
    return _drain(engine)


def _arrival_joins_chain(engine) -> dict:
    engine.add_request("a", prompt_token_ids=list(range(2, 9)),
                       sampling=_greedy(12))
    tokens = {}
    for _ in range(4):
        for out in engine.step():
            tokens.setdefault(out.request_id, []).extend(out.new_token_ids)
    engine.add_request("b", prompt_token_ids=[9, 8, 7, 6], sampling=_greedy(6))
    for rid, toks in _drain(engine).items():
        tokens.setdefault(rid, []).extend(toks)
    return tokens


def _periodic_prompt(engine) -> dict:
    engine.add_request("spec", prompt_token_ids=[5, 6, 7, 5, 6, 7, 5, 6],
                       sampling=_greedy(6))
    tokens = _drain(engine)
    assert engine.spec_proposed_total > 0, "the verify step never ran"
    return tokens


def _embeddings(engine) -> dict:
    return {"short": engine.runner.encode([1, 2, 3]).tolist(),
            "long": engine.runner.encode(list(range(2, 40))).tolist()}


def _is(kind, extras=None):
    """Does a shape key of the holder name this family's program?"""
    def match(key):
        if key[1] != kind:
            return False
        return extras is None or extras(key[3])
    return match


SYNC = dict(overlap_decode=False, num_decode_steps=1)
# family -> (engine settings over TINY, the traffic, which key is its own)
FAMILIES = {
    "prefill": (SYNC, _two_requests, _is("prefill")),
    "decode": (SYNC, _two_requests, _is("decode", lambda e: len(e) == 2)),
    "chained_step": (dict(num_decode_steps=1), _two_requests,
                     _is("decode", lambda e: len(e) == 3 and e[0] == 1)),
    "deeper_burst": ({}, _two_requests,
                     _is("decode", lambda e: len(e) == 3 and e[0] == 2)),
    "splice": (dict(num_decode_steps=1), _arrival_joins_chain, _is("splice")),
    "spec_verify": (dict(max_num_seqs=1, speculative_ngram=2,
                         num_decode_steps=1), _periodic_prompt,
                    _is("spec_verify")),
    "encode": ({}, _embeddings, _is("encode")),
}


def _serve(over: dict, traffic, cache_dir=None, listen=False):
    """(what the traffic returned, the engine's holder, first-met shapes,
    store outcomes) of a fresh engine. ``listen``: keep jax's duration
    events of its steps (its start lowers the programs that make the
    weights and the cache)."""
    before, c0 = _outcomes(), ENGINE_TELEMETRY.compile_count()
    engine = LLMEngine(EngineConfig(**{
        **TINY, **over, "compile_cache_dir": cache_dir}))
    del _events[:]
    if listen:
        _listening.append(1)
    try:
        result = traffic(engine)
    finally:
        del _listening[:]
    holder = engine.runner.programs
    met = ENGINE_TELEMETRY.compile_count() - c0
    del engine
    gc.collect()
    return result, holder, met, _since(before)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_stored_tree_serves_the_same_tokens_with_nothing_lowered(
        family, placed, monkeypatch):
    over, traffic, own = FAMILIES[family]
    # traced, as every engine was: the session's cache, no store (the CPU
    # under the variable alone)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", SESSION_CACHE_DIR)
    want, holder, _, made = _serve(over, traffic)
    assert holder.store is None and made == dict.fromkeys(made, 0)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")

    built, holder, met, made = _serve(over, traffic, placed)
    assert built == want
    assert made["built"] >= met > 0 and made["loaded"] == made["rejected"] == 0
    n_built = made["built"]

    loaded, holder, met_again, made = _serve(over, traffic, placed, listen=True)
    assert loaded == want, "a loaded program computed other tokens"
    assert made == {"loaded": n_built, "built": 0, "rejected": 0}
    assert met_again == met
    if family in ("chained_step", "deeper_burst", "splice"):
        # a chain's first start loads its splices too, one a row bucket
        assert made["loaded"] > met
    else:
        assert made["loaded"] == met
    assert not [e for e in _events
                if e.endswith(("jaxpr_to_mlir_module_duration",
                               "backend_compile_duration"))], _events
    mine = [p for k, p in holder._programs.items() if own(k)]
    assert mine and all(p.name for p in mine), (family, list(holder._programs))


def test_a_recurrent_class_with_state_slots_is_loaded_too(placed, monkeypatch):
    prompts = [list(range(3, 12)), [7, 8, 9, 10]]

    def go(cache_dir):
        before = _outcomes()
        eng = contract.make_engine(
            "tiny-nemotron-h-debug", compile_cache_dir=cache_dir,
            enable_prefix_caching=False)
        assert eng.runner.state_slots > 0
        out = contract.run(eng, prompts, 6, stagger=2)
        tokens = [(r["tokens"], r["logprobs"]) for r in out]
        del eng, out
        gc.collect()
        return tokens, _since(before)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", SESSION_CACHE_DIR)
    want, _ = go(None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    built, made = go(placed)
    assert built == want and made["built"] > 0 and made["loaded"] == 0
    loaded, again = go(placed)
    assert loaded == want, "tokens or log-probabilities differ"
    assert again == {"loaded": made["built"], "built": 0, "rejected": 0}


# ----------------------------------------------------------------------
# What names an entry
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def wiring_inputs():
    cfg = EngineConfig(**TINY)
    return cfg, get_model_config(cfg.model), build_mesh(MeshConfig())


def _wiring(inputs, cfg=None, **resolved):
    base, model_cfg, mesh = inputs
    return wiring_digest(cfg or base, model_cfg, mesh,
                         {"kv_pages": 16, **resolved})


def _other(value):
    if isinstance(value, bool):
        return not value
    if isinstance(value, int):
        return value + 1
    if isinstance(value, float):
        return value + 0.5
    return f"{value}x"


@pytest.mark.parametrize(
    "field", [f.name for f in dataclasses.fields(EngineConfig)])
def test_every_engine_setting_names_the_entry_but_where_things_are(
        field, wiring_inputs):
    cfg = wiring_inputs[0]
    changed = dataclasses.replace(cfg, **{field: _other(getattr(cfg, field))})
    same = _wiring(wiring_inputs) == _wiring(wiring_inputs, changed)
    assert same == (field in PLACEMENT_FIELDS), field


def _source_byte(monkeypatch, tmp_path, inputs):
    tree = tmp_path / "pkg"
    (tree / "ops").mkdir(parents=True)
    (tree / "ops" / "kernel.py").write_text("CHUNK = 128\n")
    (tree / "notes.txt").write_text("not code")
    monkeypatch.setattr(program_store, "_PACKAGE_ROOT", str(tree))
    before = source_digest(), _wiring(inputs)
    (tree / "notes.txt").write_text("still not code")
    assert (source_digest(), _wiring(inputs)) == before
    (tree / "ops" / "kernel.py").write_text("CHUNK = 129\n")
    return before[1], _wiring(inputs)


def _resolved_pages(monkeypatch, tmp_path, inputs):
    return _wiring(inputs), _wiring(inputs, kv_pages=17)


def _jax_version(monkeypatch, tmp_path, inputs):
    before = _wiring(inputs)
    real = importlib.metadata.version
    monkeypatch.setattr(
        importlib.metadata, "version",
        lambda name: "0.9.1" if name == "jax" else real(name))
    return before, _wiring(inputs)


def _xla_flags(monkeypatch, tmp_path, inputs):
    before = _wiring(inputs)
    monkeypatch.setenv(
        "XLA_FLAGS", os.environ.get("XLA_FLAGS", "") + " --xla_cpu_enable_fast_math=true")
    return before, _wiring(inputs)


def _libtpu_args(monkeypatch, tmp_path, inputs):
    before = _wiring(inputs)
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_tpu_scoped_vmem_limit_kib=65536")
    return before, _wiring(inputs)


def _jax_config(monkeypatch, tmp_path, inputs):
    before = _wiring(inputs)
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        return before, _wiring(inputs)
    finally:
        jax.config.update("jax_default_matmul_precision", old)


def _model_config(monkeypatch, tmp_path, inputs):
    cfg, model_cfg, mesh = inputs
    other = dataclasses.replace(model_cfg, rope_theta=model_cfg.rope_theta * 2)
    return _wiring(inputs), _wiring((cfg, other, mesh))


@pytest.mark.parametrize("change", [
    _source_byte, _resolved_pages, _jax_version, _xla_flags, _libtpu_args,
    _jax_config, _model_config], ids=lambda f: f.__name__.strip("_"))
def test_the_wiring_changes_with(change, monkeypatch, tmp_path, wiring_inputs):
    before, after = change(monkeypatch, tmp_path, wiring_inputs)
    assert before != after


def _args(pages=16, rows=2, width=4):
    put = jax.device_put
    return ({"w": put(jnp.zeros((8, 8), jnp.bfloat16))},
            {"k": put(jnp.zeros((pages, 16, 2, 8), jnp.bfloat16))},
            {"tokens": put(np.zeros((rows, 1), np.int32)),
             "block_tables": put(np.zeros((rows, width), np.int32))})


@pytest.mark.parametrize("what,other", [
    ("want_lp", dict(static=(True, True))),
    ("greedy", dict(static=(False, False))),
    ("num_kv_blocks", dict(args=_args(pages=17))),
    ("padded_rows", dict(args=_args(rows=4))),
    ("table_width", dict(args=_args(width=8))),
    ("function", dict(jitted=jax.jit(lambda *a: a[0], static_argnums=(3, 4)))),
    ("wiring", dict(wiring="b" * 64)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_name_changes_with(what, other):
    def step(params, kv, batch, want_lp, greedy):
        return params

    base = dict(wiring="a" * 64, jitted=jax.jit(step, static_argnums=(3, 4)),
                args=_args(), static=(False, True))
    assert program_name(**base) == program_name(**{**base, "args": _args()})
    assert program_name(**base) != program_name(**{**base, **other}), what


# ----------------------------------------------------------------------
# An entry is never trusted
# ----------------------------------------------------------------------


def _entries(cache_dir) -> list:
    found = []
    for folder, _, files in os.walk(cache_dir):
        if os.path.basename(folder) == program_store.PROGRAMS_DIR:
            found += [os.path.join(folder, f) for f in sorted(files)]
    return found


def _truncate(paths):
    for p in paths:
        with open(p, "rb") as f:
            blob = f.read()
        with open(p, "wb") as f:
            f.write(blob[: len(blob) // 2])


def _garbage(paths):
    rng = np.random.default_rng(0)
    for p in paths:
        with open(p, "wb") as f:
            f.write(rng.bytes(4096))


def _anothers_program(paths):
    """Each entry keeps its name and gets the next one's program: it reads
    and loads, and refuses the arguments it is then given."""
    entries = []
    for p in paths:
        with open(p, "rb") as f:
            entries.append(pickle.loads(zlib.decompress(f.read())))
    for i, p in enumerate(paths):
        swapped = dict(entries[(i + 1) % len(entries)], name=entries[i]["name"])
        with open(p, "wb") as f:
            f.write(zlib.compress(pickle.dumps(swapped), 1))


@pytest.mark.parametrize(
    "spoil", [_truncate, _garbage, _anothers_program],
    ids=["truncated", "garbage", "refuses_its_arguments"])
def test_a_bad_entry_is_rejected_rebuilt_rewritten_and_the_request_served(
        spoil, placed):
    want, _, _, made = _serve(SYNC, _two_requests, placed)
    paths = _entries(placed)
    assert len(paths) == made["built"] > 1
    spoil(paths)
    served, _, _, made = _serve(SYNC, _two_requests, placed)
    assert served == want
    assert made["rejected"] == made["built"] == len(paths), made
    assert made["loaded"] == 0
    assert _entries(placed) == paths, "rewritten under the same names"
    again, _, _, made = _serve(SYNC, _two_requests, placed)
    assert again == want
    assert made == {"loaded": len(paths), "built": 0, "rejected": 0}


def test_a_step_program_lies_on_the_volume_once(placed):
    """A program built for the store goes past XLA's persistent cache (which
    keeps the engine's other programs, as ever), and is counted where a
    compile always was: a miss when built, a hit when loaded."""
    h0, m0 = ENGINE_TELEMETRY.cache_stats()
    _, _, _, made = _serve(SYNC, _two_requests, placed)
    h1, m1 = ENGINE_TELEMETRY.cache_stats()
    paths = _entries(placed)
    assert len(paths) == made["built"] > 1 and m1 - m0 >= made["built"]
    xla = os.path.dirname(os.path.dirname(paths[0]))
    kept = [n for n in os.listdir(xla) if n != program_store.PROGRAMS_DIR]
    assert kept, "the programs that make weights and cache are XLA's to keep"
    assert not [n for n in kept if "_step" in n or "splice" in n], kept
    # and jax's cache is in use again after a build
    from jax._src import compilation_cache
    assert compilation_cache.is_cache_used(jax.devices()[0].client)
    _, _, _, again = _serve(SYNC, _two_requests, placed)
    h2, m2 = ENGINE_TELEMETRY.cache_stats()
    assert again["loaded"] == len(paths) and m2 == m1
    assert h2 - h1 >= len(paths)
    assert sorted(os.listdir(xla)) == sorted(kept + [program_store.PROGRAMS_DIR])


def test_a_volume_that_refuses_utime_still_gives_its_entries(
        placed, monkeypatch):
    want, _, _, made = _serve(SYNC, _two_requests, placed)

    def refused(path, *a, **kw):
        raise PermissionError(30, "Read-only file system", path)

    monkeypatch.setattr(program_store.os, "utime", refused)
    served, _, _, again = _serve(SYNC, _two_requests, placed)
    assert served == want
    assert again == {"loaded": made["built"], "built": 0, "rejected": 0}


def _toy_store(tmp_path):
    return ProgramStore(str(tmp_path), "w" * 64, jax.devices()[:1])


def test_a_later_call_refused_goes_to_the_jit_and_the_entry_stays(tmp_path):
    """The entry served the key's first call; that a later call of this
    process brings other arguments under the key is not the entry's fault."""
    double = jax.jit(lambda x: x * 2)
    first = StepPrograms(_toy_store(tmp_path))
    assert first.call(("k",), double, (jnp.arange(4),)).tolist() == [0, 2, 4, 6]
    (entry,) = os.listdir(tmp_path)
    before = _outcomes()
    calls = StepPrograms(_toy_store(tmp_path))
    assert calls.call(("k",), double, (jnp.arange(4),)).tolist() == [0, 2, 4, 6]
    assert calls.call(("k",), double, (jnp.arange(8),)).tolist() == list(
        range(0, 16, 2))
    assert _since(before) == {"loaded": 1, "built": 0, "rejected": 1}
    assert os.listdir(tmp_path) == [entry]
    # the key's later calls are the jit's: nothing more is counted
    assert calls.call(("k",), double, (jnp.arange(4),)).tolist() == [0, 2, 4, 6]
    assert _since(before)["rejected"] == 1


@pytest.mark.parametrize("name,age_days,stays", [
    ("w" * 16 + "-mine" + ".z", 400, True),      # this wiring's, however old
    ("v" * 16 + "-anothers" + ".z", 1, True),    # a rollback may want it
    ("v" * 16 + "-anothers" + ".z", 15, False),
    ("tmpab12cd.tmp", 15, False),                 # a writer that died
], ids=["this_wirings_old", "anothers_fresh", "anothers_stale", "dead_writers"])
def test_a_store_that_opens_sweeps_what_other_wirings_left(
        name, age_days, stays, tmp_path):
    import time

    path = tmp_path / name
    path.write_bytes(b"x")
    then = time.time() - age_days * 86400
    os.utime(path, (then, then))
    _toy_store(tmp_path)
    assert path.exists() == stays


def test_with_dp_a_continuation_is_a_program_of_its_own(placed, monkeypatch):
    """With dp > 1 a chain's start takes ``tokens`` by rows and its
    continuation takes them replicated, as ever: two programs, each under
    its key, both stored, neither refused."""
    over = dict(data_parallel_size=2, num_decode_steps=1)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", SESSION_CACHE_DIR)
    want, _, _, _ = _serve(over, _two_requests)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    built, holder, _, made = _serve(over, _two_requests, placed)
    assert built == want and made["rejected"] == 0
    keys = [k for k in holder._programs if k[1] == "decode"]
    continued = [k for k in keys if k[-1] == "continued"]
    assert continued and all(k[:-1] in keys for k in continued)
    by_rows = [k for k in continued
               if holder._programs[k].name != holder._programs[k[:-1]].name]
    assert by_rows, "no batch was split by rows: the case shows nothing"
    loaded, _, _, again = _serve(over, _two_requests, placed)
    assert loaded == want
    assert again == {"loaded": made["built"], "built": 0, "rejected": 0}


_WRITER = """
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from production_stack_tpu.engine.program_store import ProgramStore
compiled = jax.jit(lambda x: x * 2 + 1).lower(jnp.zeros((64,), jnp.float32)).compile()
store = ProgramStore(sys.argv[2], "w" * 64, jax.devices()[:1])
while time.time() < float(sys.argv[3]):
    time.sleep(0.001)
for _ in range(40):
    store.write("shared", compiled)
"""


def test_two_processes_writing_one_entry_leave_a_loadable_one(tmp_path):
    import time

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    start = str(time.time() + 8)
    writers = [subprocess.Popen(
        [sys.executable, "-c", _WRITER, root, str(tmp_path), start], env=env)
        for _ in range(2)]
    assert [w.wait(timeout=120) for w in writers] == [0, 0]
    assert os.listdir(tmp_path) == ["w" * 16 + "-shared.z"], (
        "no temporary file is left")
    program = _toy_store(tmp_path).load("shared")
    assert program is not None
    out = program(jnp.arange(64, dtype=jnp.float32))
    assert np.array_equal(np.asarray(out), np.arange(64) * 2 + 1)


def test_the_donated_cache_is_deleted_after_a_loaded_programs_call(placed):
    _serve(SYNC, _two_requests, placed)
    before = _outcomes()
    engine = LLMEngine(EngineConfig(**{
        **TINY, **SYNC, "compile_cache_dir": placed}))
    engine.add_request("a", prompt_token_ids=list(range(2, 9)),
                       sampling=_greedy(3))
    for _ in range(3):
        cache = jax.tree.leaves(engine.runner.kv_cache)
        engine.step()
        assert all(x.is_deleted() for x in cache)
        assert not any(
            x.is_deleted() for x in jax.tree.leaves(engine.runner.kv_cache))
    assert _since(before)["loaded"] >= 2 and _since(before)["built"] == 0


# ----------------------------------------------------------------------
# Where there is a store
# ----------------------------------------------------------------------


def _no_placed_cache(monkeypatch, cfg, path):
    return None, cfg


def _several_processes(monkeypatch, cfg, path):
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    return path, dataclasses.replace(cfg, compile_cache_dir=path)


def _cpu_under_the_variable_alone(monkeypatch, cfg, path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
    return path, cfg


@pytest.mark.parametrize("case", [
    _no_placed_cache, _several_processes, _cpu_under_the_variable_alone],
    ids=lambda f: f.__name__.strip("_"))
def test_no_store(case, monkeypatch, tmp_path, wiring_inputs):
    cfg, model_cfg, mesh = wiring_inputs
    path, cfg = case(monkeypatch, cfg, str(tmp_path))
    assert open_store(path, cfg, model_cfg, mesh, {}) is None
    assert not os.path.exists(tmp_path / program_store.PROGRAMS_DIR)
    # and the holder without one calls the jit, whatever the key
    calls = StepPrograms()
    double = jax.jit(lambda x, n: x * n, static_argnums=(1,))
    assert int(calls.call(("k",), double, (jnp.int32(3),), (2,))) == 6
    assert int(calls.call(None, double, (jnp.int32(3),), (3,))) == 9


def test_a_store_where_the_engines_configuration_places_the_cache(
        tmp_path, wiring_inputs):
    cfg, model_cfg, mesh = wiring_inputs
    cfg = dataclasses.replace(cfg, compile_cache_dir=str(tmp_path))
    store = open_store(str(tmp_path), cfg, model_cfg, mesh, {})
    assert store.path == str(tmp_path / program_store.PROGRAMS_DIR)
    assert os.path.isdir(store.path)


def test_precompile_on_a_stored_tree_reports_the_same_buckets(placed):
    def go():
        before, c0 = _outcomes(), ENGINE_TELEMETRY.compile_count()
        engine = LLMEngine(EngineConfig(**{
            **TINY, "compile_cache_dir": placed, "warmup": "full",
            "warmup_bucket_budget": 10}))
        s0 = _first_use_seconds()
        summary = engine.precompile()
        assert ENGINE_TELEMETRY_REGISTRY.get_sample_value(
            "pst_engine_startup_seconds", {"phase": "program_first_use"}) > 0
        del engine
        gc.collect()
        return (summary, ENGINE_TELEMETRY.compile_count() - c0,
                _since(before), _first_use_seconds() - s0)

    cold, met, made, cold_s = go()
    assert cold["buckets_compiled"] == 10 == met and made["built"] >= 10
    warm, met_again, again, warm_s = go()
    assert {k: v for k, v in warm.items() if k != "seconds"} == {
        k: v for k, v in cold.items() if k != "seconds"}
    assert met_again == met
    assert again == {"loaded": made["built"], "built": 0, "rejected": 0}
    assert warm["seconds"] < cold["seconds"] and 0 < warm_s < cold_s


def test_the_directory_can_be_deleted_at_any_time(placed):
    want, _, _, made = _serve(SYNC, _two_requests, placed)
    for path in _entries(placed):
        shutil.rmtree(os.path.dirname(path), ignore_errors=True)
    again, _, _, rebuilt = _serve(SYNC, _two_requests, placed)
    assert again == want and rebuilt == made
