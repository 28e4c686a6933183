"""Edge coverage for config/quantization/server-validation paths added in r4."""

import numpy as np
import pytest

pytestmark = pytest.mark.fast


def test_kv_sizing_on_a_chip(monkeypatch):
    """On a chip the page count comes from the backend's ``bytes_limit``,
    else from the device table; a chip that offers neither is an error
    (it used to fall through to a 512 MiB "virtual CPU" pool)."""
    import jax

    from production_stack_tpu.engine.config import (
        EngineConfig,
        resolve_num_kv_blocks,
    )
    from production_stack_tpu.models.registry import get_model_config

    class FakeDev:
        platform = "tpu"
        device_kind = "TPU v5 lite"
        stats = {}

        def memory_stats(self):
            return self.stats

    dev = FakeDev()
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    cfg = EngineConfig(
        model="llama-3-8b", max_model_len=32768, block_size=128,
        kv_cache_dtype="float8_e4m3fn", hbm_utilization=0.88,
    )
    mcfg = get_model_config("llama-3-8b")
    # int8 8B params ≈ 8.06e9 bytes on one chip.
    n = resolve_num_kv_blocks(cfg, mcfg, 8_060_000_000)
    # 16 GiB * 0.88 - params ≈ 7.06 GiB -> ~840 pages of 8.39 MB.
    assert 700 < n < 1000, n

    # The backend's own limit wins over the table.
    dev.stats = {"bytes_limit": 32 * 1024**3}
    assert resolve_num_kv_blocks(cfg, mcfg, 8_060_000_000) > 2 * n

    dev.stats, dev.device_kind = {}, "mystery"
    with pytest.raises(RuntimeError, match="DEVICE_TABLE"):
        resolve_num_kv_blocks(cfg, mcfg, 8_060_000_000)

    # A failing memory_stats() is not swallowed either.
    def boom():
        raise OSError("stats unavailable")

    dev.memory_stats = boom
    with pytest.raises(OSError):
        resolve_num_kv_blocks(cfg, mcfg, 8_060_000_000)


def test_logit_bias_validation():
    from production_stack_tpu.engine.server import _parse_logit_bias

    assert _parse_logit_bias(None) == ()
    assert _parse_logit_bias({"5": 10.0}) == ((5, 10.0),)
    with pytest.raises(ValueError, match="integer"):
        _parse_logit_bias({"not-an-id": 1.0})
    with pytest.raises(ValueError, match=r"\[-100, 100\]"):
        _parse_logit_bias({"5": 101.0})
    with pytest.raises(ValueError, match=r"\[-100, 100\]"):
        _parse_logit_bias({"5": -150.0})


def test_np_quantize_bf16_bit_pattern():
    """Host-side quantization of raw-bf16 safetensors payloads (uint16 bit
    patterns) must dequantize close to the true values."""
    import ml_dtypes

    from production_stack_tpu.models.llama import _np_quantize

    rng = np.random.default_rng(0)
    true = rng.normal(size=(32, 16)).astype(ml_dtypes.bfloat16)
    raw = true.view(np.uint16)  # what safetensors hands the loader
    q, s = _np_quantize(raw, axis=-2)
    assert q.dtype == np.int8 and s.shape == (16,)
    deq = q.astype(np.float32) * s[None, :]
    err = np.abs(deq - true.astype(np.float32))
    assert np.all(err <= s[None, :] * 0.5 + 1e-6)


def test_extproc_picker_client_static_pods():
    from production_stack_tpu.gateway.extproc import PickerClient

    pc = PickerClient(
        "http://localhost:1", pods=[{"name": "a", "address": "1.2.3.4:8000"}]
    )
    assert pc.resolve_pods() == [{"name": "a", "address": "1.2.3.4:8000"}]
    # Picker unreachable -> graceful None (gateway continues unrouted).
    assert pc.pick("m", "prompt") is None


def test_extproc_picker_client_dns(monkeypatch):
    import socket

    from production_stack_tpu.gateway.extproc import PickerClient

    def fake_getaddrinfo(host, port, proto=None):
        assert host == "engines-headless"
        return [
            (socket.AF_INET, None, None, "", ("10.0.0.2", port)),
            (socket.AF_INET, None, None, "", ("10.0.0.1", port)),
            (socket.AF_INET, None, None, "", ("10.0.0.2", port)),  # dup
        ]

    monkeypatch.setattr(socket, "getaddrinfo", fake_getaddrinfo)
    pc = PickerClient("http://localhost:1", pods_dns="engines-headless",
                      pods_port=8000)
    pods = pc.resolve_pods()
    assert [p["address"] for p in pods] == ["10.0.0.1:8000", "10.0.0.2:8000"]
