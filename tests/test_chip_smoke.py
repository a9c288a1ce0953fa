"""chip_smoke.py stays runnable: its refusal off-TPU, and one leg of it
at a tiny size on the CPU mesh (the interpreter runs the kernels; the
two TPU-only facts — the Mosaic custom call in the lowered step and
the fused EP transport — are what ``on_chip=False`` leaves out)."""

import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_refuses_to_run_without_a_tpu():
    """Under JAX_PLATFORMS=cpu: non-zero exit, the reason on the last
    line, and no result line."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    lines = out.stdout.strip().splitlines()
    assert lines[-1].startswith("chip_smoke: FAIL:")
    assert "not a TPU" in lines[-1]
    assert not any('"ok"' in ln for ln in lines)


@pytest.fixture
def tiny(monkeypatch):
    from triton_distributed_tpu.models import presets

    monkeypatch.setattr(
        chip_smoke, "model_config",
        lambda: presets.tiny(presets.deepseek_moe_16b()))
    monkeypatch.setattr(chip_smoke, "ENGINE", dict(
        slots=4, token_budget=64, chunk=16, page=8, npages=64))
    monkeypatch.setattr(chip_smoke, "TRACE", dict(
        n_requests=6, mean_interarrival=1.0, len_lo=16, len_hi=48,
        max_new_lo=4, max_new_hi=8))
    monkeypatch.setattr(chip_smoke, "PARITY_ENGINE", dict(
        slots=4, token_budget=64, chunk=32, page=8, npages=32))


def test_leg_passes_its_own_checks_at_tiny_size(tiny, tmp_path,
                                                monkeypatch, n=4):
    """The four-device leg: everything the one-device leg does, plus
    the spread check and the pinned fused overlap ops."""
    monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
    rec = chip_smoke.leg(jax.devices()[:n], on_chip=False)
    assert rec["tp"] == n and rec["requests"] == 6
    for view, (_, tol) in chip_smoke.PARITY_VIEWS.items():
        got = rec["parity"][view]
        assert got["rows_q_lens"] == [1, 32]          # a MIXED batch
        assert got["rms_rel_err"] <= tol
    assert not rec["degraded"] and not rec["failures"]
    assert rec["programs_lowered"]["warm_pass"] == 0
    # the two passes launched ahead; the third ran in the drained order
    assert 0 < rec["lookahead"]["steps_ahead"] < rec["lookahead"]["steps"]
    assert rec["lookahead"]["drained_pass_steps"] > 0
    assert set(rec["overlap_ops"]) == {"ag_gemm", "gemm_rs"}


def test_leg_fails_when_the_engine_swallowed_a_failure(tiny, tmp_path,
                                                       monkeypatch):
    """The smoke asks the engine to PROPAGATE: a kernel failure the
    degrade path would have absorbed fails the leg instead."""
    import triton_distributed_tpu.kernels.ragged_paged_attention as rpa

    def boom(*a, **k):
        raise RuntimeError("injected kernel failure")

    monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
    monkeypatch.setattr(rpa, "ragged_paged_attention", boom)
    # hidden=64 keeps this model off the step-jit caches of the leg
    # above (a traced step captured the real kernel)
    from triton_distributed_tpu.models import presets

    monkeypatch.setattr(
        chip_smoke, "model_config",
        lambda: presets.tiny(presets.deepseek_moe_16b(), hidden=64))
    with pytest.raises(RuntimeError, match="injected kernel failure"):
        chip_smoke.leg(jax.devices()[:1], on_chip=False)


def test_window_leg_passes_its_own_checks_at_tiny_size(tmp_path,
                                                       monkeypatch):
    """The window leg (sliding-window layers over ring pools, an expert
    share, a shared expert) at CPU sizes: kernels against their XLA
    twins, the ring pools' size, and the "published" rung lowered."""
    small = dict(hidden=64, ffn=64, dense_ffn=96, n_heads=4, n_kv_heads=2,
                 head_dim=16, vocab=128, num_experts=8, topk=2, window=16)
    monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
    monkeypatch.setattr(chip_smoke, "WINDOW_TWIN", dict(
        n_layers=5, experts_held=4, first_expert_held=2, **small))
    monkeypatch.setattr(chip_smoke, "WINDOW_ENGINE", dict(
        slots=4, token_budget=64, chunk=24, page=8, npages=32))
    monkeypatch.setattr(chip_smoke, "WINDOW_PROMPTS", (70, 5, 33))
    monkeypatch.setattr(chip_smoke, "PUBLISHED_CUT", dict(
        n_layers=5, experts_held=2, **small))
    monkeypatch.setattr(chip_smoke, "PUBLISHED_ENGINE", dict(
        slots=4, token_budget=64, chunk=16, page=8, npages=32))
    rec = chip_smoke.window_leg(jax.devices()[:1], on_chip=False)
    assert rec["twin_rel_rms"] <= chip_smoke.WINDOW_TOL
    assert rec["ring_pages_per_slot"] == 6      # ceil((24 + 15) / 8) + 1
    assert 0 < rec["window_pages_walked"] < rec["global_pages_walked"]


def test_mla_leg_passes_its_own_checks_at_tiny_size(tmp_path, monkeypatch):
    """The mla leg (a latent pool served by the latent walk and the
    one-pool append, against their XLA twins) at CPU sizes."""
    monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
    monkeypatch.setattr(chip_smoke, "MLA_TWIN", dict(
        n_layers=3, n_dense_layers=1, hidden=64, ffn=64, dense_ffn=96,
        n_heads=4, n_kv_heads=4, head_dim=12, q_latent=24, kv_latent=16,
        qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, vocab=128,
        num_experts=16, topk=4, router_groups=4, router_topk_groups=2,
        experts_held=4, first_expert_held=8, dtype=jnp.float32))
    monkeypatch.setattr(chip_smoke, "MLA_ENGINE", dict(
        slots=4, token_budget=64, chunk=16, page=16, npages=32))
    monkeypatch.setattr(chip_smoke, "MLA_PROMPTS", (70, 5, 33))
    rec = chip_smoke.mla_leg(jax.devices()[:1])
    assert rec["twin_rel_rms"] <= chip_smoke.MLA_TOL
    assert rec["latent_rows"] > 0 and rec["append_runs"] > 0
    assert rec["latent_pages_walked"] > 0
