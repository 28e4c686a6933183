"""The device path is resolved, never guessed (production_stack_tpu/device.py).

No chip is needed: the backend is monkeypatched. Covers the refusals PR 21
put where fallbacks used to hide the device — an engine that finds no
accelerator, interpret mode inherited on a chip, a device kind with no
known memory, an int4 engine on a multi-device mesh on tpu, a prefill chunk the
q tile does not divide — the compile-cache placement rule, the per-shard
attention wrapper, and ``chip_smoke.py`` failing at once on the CPU.
"""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu import device
from production_stack_tpu.engine import precompile
from production_stack_tpu.engine import runner as runner_mod
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.models.registry import get_model_config

pytestmark = pytest.mark.fast

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny(**kw) -> EngineConfig:
    return EngineConfig(
        model="tiny-llama-debug", max_model_len=64, block_size=8,
        num_kv_blocks=16, max_num_seqs=2, max_prefill_tokens=16, **kw,
    )


# ---------------------------------------------------------------------------
# Platform rule
# ---------------------------------------------------------------------------


def test_engine_refuses_a_silent_cpu_fallback(monkeypatch):
    """JAX dropped to the CPU (no chip found) and nobody asked for the CPU:
    the engine stops at start-up instead of serving at interpreter speed."""
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="not 'tpu'.*JAX_PLATFORMS=cpu"):
        LLMEngine(_tiny())
    # Any non-tpu backend, e.g. a GPU, is refused the same way.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not 'tpu'"):
        device.resolve_platform()


def test_cpu_only_by_explicit_request(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert device.resolve_platform() == "cpu"
    assert device.pallas_interpret() is True


def test_interpret_variable_is_refused_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv(device.INTERPRET_ENV, "1")
    with pytest.raises(RuntimeError, match="interpret mode belongs to the CPU"):
        device.resolve_platform()
    monkeypatch.delenv(device.INTERPRET_ENV)
    assert device.resolve_platform() == "tpu"
    # On the chip the kernels compile, whatever the environment says.
    monkeypatch.setenv(device.INTERPRET_ENV, "1")
    assert device.pallas_interpret() is False


def test_int4_kernel_selection_is_platform_and_shape_only(monkeypatch):
    from production_stack_tpu.ops.int4_matmul import use_int4_kernel

    big = (jax.ShapeDtypeStruct((2048, 4096), jnp.int8),
           jax.ShapeDtypeStruct((32, 4096), jnp.float32))
    tiny = (jax.ShapeDtypeStruct((32, 64), jnp.int8),
            jax.ShapeDtypeStruct((1, 64), jnp.float32))
    monkeypatch.delenv(device.INTERPRET_ENV, raising=False)
    assert use_int4_kernel(*big) is False  # cpu: XLA dequant
    monkeypatch.setenv(device.INTERPRET_ENV, "1")
    assert use_int4_kernel(*big) is True  # cpu, kernel interpreted (tests)
    assert use_int4_kernel(*tiny) is False
    monkeypatch.delenv(device.INTERPRET_ENV)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert use_int4_kernel(*big) is True  # tpu: always the compiled kernel
    assert use_int4_kernel(*tiny) is False


@pytest.mark.parametrize(
    "parallel", [dict(tensor_parallel_size=4), dict(data_parallel_size=2)]
)
def test_int4_on_a_multi_device_mesh_is_refused_on_tpu(monkeypatch, parallel):
    """A Mosaic kernel cannot be partitioned by GSPMD and the int4 matmul
    has no per-shard wrapper: int4 on more than one device does not start
    on the chip (it used to die at the first request)."""
    monkeypatch.setattr(runner_mod, "resolve_platform", lambda: "tpu")
    with pytest.raises(ValueError, match="int4.*more than one device"):
        LLMEngine(_tiny(quantization="int4", **parallel))


# ---------------------------------------------------------------------------
# One device table
# ---------------------------------------------------------------------------


def test_unknown_device_kind_has_no_size():
    for kind in ("cpu", None, "TPU v9000"):
        with pytest.raises(RuntimeError, match="DEVICE_TABLE"):
            device.require_device_spec(kind)
    assert device.require_device_spec("TPU v5 lite").hbm_bytes == 16 * 1024**3


# ---------------------------------------------------------------------------
# Compile cache placement
# ---------------------------------------------------------------------------


@pytest.fixture
def cache_config_restored():
    from jax._src import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_env_wins_and_code_sets_no_directory(
    monkeypatch, tmp_path, cache_config_restored
):
    """With JAX_COMPILATION_CACHE_DIR set, the program uses that directory
    as is: no jax.config.update of the directory, no keyed subdirectory."""
    env_dir = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv(precompile.CACHE_DIR_ENV, env_dir)
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1],
    )
    cfg = _tiny(compile_cache_dir=str(tmp_path / "flag"))
    got = precompile.configure_compile_cache(cfg, get_model_config(cfg.model))
    assert got == env_dir
    assert "jax_compilation_cache_dir" not in updates
    assert not (tmp_path / "flag").exists()


def test_compile_cache_unset_uses_flag_then_fixed_checkout_path(
    monkeypatch, tmp_path, cache_config_restored
):
    monkeypatch.delenv(precompile.CACHE_DIR_ENV, raising=False)
    mcfg = get_model_config("tiny-llama-debug")
    # The deployment flag keeps its meaning: <dir>/<key>.
    cfg = _tiny(compile_cache_dir=str(tmp_path / "pvc"))
    got = precompile.configure_compile_cache(cfg, mcfg)
    assert got == os.path.join(
        str(tmp_path / "pvc"), precompile.compile_cache_key(cfg, mcfg)
    )
    assert jax.config.jax_compilation_cache_dir == got
    # Nothing placed, on the CPU test platform: off.
    assert precompile.configure_compile_cache(_tiny(), mcfg) is None
    # Nothing placed, on the chip: ONE fixed path inside the checkout —
    # no temporary name, pid or time in it — and it is git-ignored.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    got = precompile.configure_compile_cache(_tiny(), mcfg)
    assert got == os.path.join(REPO, ".jax_cache")
    assert got == precompile.DEFAULT_COMPILE_CACHE_DIR
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_temporary_cache_directories_in_the_tree():
    """Nothing on the serving path builds a compile-cache directory from
    a temporary name."""
    for rel in ("chip_smoke.py",
                "production_stack_tpu/engine/precompile.py",
                "production_stack_tpu/engine/engine.py"):
        with open(os.path.join(REPO, rel)) as f:
            src = f.read()
        assert "mkdtemp" not in src and "TemporaryDirectory" not in src, rel


# ---------------------------------------------------------------------------
# Kernels: an error, not a detour
# ---------------------------------------------------------------------------


def _attn_inputs(B, T, H=4, KH=2, hd=32, nb=16, bs=8, W=4, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((1, nb, 2, bs, KH * hd)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(nb)[: B * W].reshape(B, W).astype(np.int32)
    )
    live = W * bs - 3
    kv_lens = jnp.full((B,), live, jnp.int32)
    q_pos = jnp.asarray(
        live - T + np.tile(np.arange(T, dtype=np.int32), (B, 1))
    )
    return q, kv, tables, kv_lens, q_pos


def test_pallas_prefill_rejects_a_chunk_its_q_tile_does_not_divide():
    from production_stack_tpu.ops.paged_attention_pallas import (
        pallas_paged_attention,
    )

    q, kv, tables, kv_lens, q_pos = _attn_inputs(1, 300, W=64, nb=80)
    with pytest.raises(ValueError, match="divisible by its q tile"):
        pallas_paged_attention(q, kv, tables, kv_lens, q_pos, scale=0.2)


def test_attention_impl_resolves_by_platform_only(monkeypatch):
    from production_stack_tpu.ops.attention import resolve_attn_impl

    assert resolve_attn_impl("auto") == "gather"
    assert resolve_attn_impl("pallas") == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_attn_impl("auto") == "pallas"
    with pytest.raises(ValueError, match="unknown attn_impl"):
        resolve_attn_impl("cuda")


@pytest.mark.parametrize("T,dp", [(8, 1), (1, 2)])
def test_pallas_attention_runs_per_shard(T, dp):
    """Heads and page lanes sharded over tp (rows over dp): the kernel runs
    once per shard under a shard_map that is manual over EVERY mesh axis —
    the only form Mosaic lowers on a multi-device mesh — and matches
    gather."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from production_stack_tpu.ops.attention import paged_attention
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(
        MeshConfig(tensor_parallel_size=2, data_parallel_size=dp)
    )
    q, kv, tables, kv_lens, q_pos = _attn_inputs(2, T)
    want = paged_attention(
        q, kv, tables, kv_lens, q_pos, scale=0.2, impl="gather"
    )
    q_s = jax.device_put(q, NamedSharding(mesh, P(None, None, "tp", None)))
    kv_s = jax.device_put(
        kv, NamedSharding(mesh, P(None, None, None, None, "tp"))
    )
    def fn(q, kv):
        return paged_attention(
            q, kv, tables, kv_lens, q_pos, scale=0.2, impl="pallas", mesh=mesh
        )

    (wrap,) = [
        e for e in jax.make_jaxpr(fn)(q_s, kv_s).jaxpr.eqns
        if e.primitive.name == "shard_map"
    ]
    assert wrap.params["manual_axes"] == frozenset(mesh.axis_names)
    np.testing.assert_allclose(
        np.asarray(jax.jit(fn)(q_s, kv_s)), np.asarray(want),
        atol=2e-5, rtol=2e-5,
    )


# ---------------------------------------------------------------------------
# What the engine says it resolved
# ---------------------------------------------------------------------------


def test_engine_states_its_device_path(monkeypatch, cache_config_restored):
    monkeypatch.delenv(precompile.CACHE_DIR_ENV, raising=False)  # the tests' own
    eng = LLMEngine(_tiny())
    info = eng.runner.device_info
    assert info["platform"] == "cpu" and info["device_kind"] == "cpu"
    assert info["attention_impl"] == "gather"  # "auto" resolved, not echoed
    assert info["int4_impl"] is None and info["pallas_interpret"] is True
    assert info["mesh_device_ids"] == [0] and info["kv_pages"] == 16
    assert info["jax"] == jax.__version__
    assert info["compile_cache_dir"] is None  # cpu, nothing placed


# ---------------------------------------------------------------------------
# chip_smoke.py: no quiet answers
# ---------------------------------------------------------------------------


def test_chip_smoke_fails_at_once_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=60,
    )
    assert time.monotonic() - t0 < 10
    assert proc.returncode != 0
    assert "JAX_PLATFORMS=cpu" in proc.stderr
    assert '"ok"' not in proc.stdout  # prints no result
