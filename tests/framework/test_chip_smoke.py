"""chip_smoke.py's phases at tiny widths on the CPU, with the Pallas
kernels interpreted — so the script that spends chip time is itself
tested before it is sent there — and its refusal to run without a TPU.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import chip_smoke  # noqa: E402
from paddle_tpu.models import (GPTConfig, JambaConfig,  # noqa: E402
                               LlamaConfig, XingConfig)


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main() == 2
    out = capsys.readouterr()
    assert out.out == ""  # no result line to mistake for a pass
    assert len(out.err.strip().splitlines()) == 1
    assert "not 'tpu'" in out.err


def test_train_phase_tiny(monkeypatch):
    # the CPU backend routes attention to XLA unless forced: the phase
    # must drive the flash fwd/dq/dkv kernels (interpreted here)
    monkeypatch.setenv("PADDLE_FLASH_FORCE", "pallas")
    facts = chip_smoke.train_phase(
        GPTConfig.tiny(), 2, 64, 4, dtype="float32", platform="cpu",
        expect_kernels=())
    assert len(facts["losses"]) == 4
    assert facts["losses"][-1] < facts["losses"][0]


def test_phases_fail_when_parameters_are_not_on_the_platform():
    # parameters on the CPU are not "on the chip"
    with pytest.raises(chip_smoke.SmokeFailure, match="parameters live"):
        chip_smoke.train_phase(
            GPTConfig.tiny(), 2, 64, 3, dtype="float32", platform="tpu",
            expect_kernels=())
    with pytest.raises(chip_smoke.SmokeFailure, match="parameters live"):
        chip_smoke.serve_phase(
            LlamaConfig.tiny(), [], 2, dtype="float32", platform="tpu",
            paged_kernel="pallas", interpret=True, tol=1e-4)


def test_train_phase_fails_without_expected_kernels(monkeypatch):
    """Interpreted kernels leave no Mosaic call in the executable: the
    check that the chip run relies on must notice."""
    monkeypatch.setenv("PADDLE_FLASH_FORCE", "pallas")
    with pytest.raises(chip_smoke.SmokeFailure, match="expected"):
        chip_smoke.train_phase(
            GPTConfig.tiny(), 2, 64, 3, dtype="float32", platform="cpu",
            expect_kernels=("flash_fwd",))


def test_serve_phase_tiny():
    # 16 pages of 2 tokens: the table length at which the engine routes
    # decode to the chunked kernel (inference.paged._CHUNK_MIN_PAGES),
    # the form the default 2048-token engine takes on the chip; the
    # per-page form is the one every other serving test runs
    engines = [("chunked", dict(max_batch=2, block_size=2, max_seq_len=32,
                                bucket_cap=32), (5, 12))]
    facts, model, prompt, logits = chip_smoke.serve_phase(
        LlamaConfig.tiny(), engines, 4, dtype="float32", platform="cpu",
        paged_kernel="pallas", interpret=True, tol=1e-4)
    assert facts["chunked"]["table_pages"] == 16
    assert facts["chunked"]["prefix_hit_blocks"] > 0
    assert facts["chunked"]["pallas_vs_dense_rel_err"] <= 1e-4
    assert logits.shape == (model.config.vocab_size,)


def test_hybrid_phase_tiny():
    """A prefill at a padded bucket and decode steps of a tiny Jamba on
    the interpreted kernels against the plain route: the state agrees
    and the idle slot's is untouched."""
    config = JambaConfig(vocab_size=256, hidden_size=32,
                         intermediate_size=64, num_layers=4, num_heads=4,
                         num_kv_heads=1, attn_layer_period=4,
                         attn_layer_offset=2, mamba_dt_rank=4)
    facts = chip_smoke.hybrid_phase(config, 20, 5, dtype="float32",
                                    platform="cpu", paged_kernel="pallas",
                                    tol=1e-4)
    assert facts["tokens"] == facts["tokens_same"] == 6
    assert facts["state_rel_err"] <= 1e-4
    assert facts["state_bytes_per_slot"] == 3 * (16 * 64 * 4 + 3 * 64 * 4)


def test_latent_phase_tiny():
    """A tiny Xing through the engine on the interpreted kernels (a
    prefix hit among its prompts), then the plain route fed the served
    tokens: every one is the plain route's choice."""
    facts = chip_smoke.latent_phase(
        XingConfig.tiny(), (40, 36, 20), 6, dtype="float32",
        platform="cpu", paged_kernel="pallas", tol=1e-4)
    assert facts["tokens"] == 18 and facts["margin"] <= 1e-4
    assert facts["prefix_hit_blocks"] >= 2
    # 5 decode steps of each request x 2 sparse layers x 2 experts a token
    assert facts["moe_rows"] == 3 * 5 * 2 * 2
    # 3 layers x (32 + 128 lanes) x 4 bytes
    assert facts["latent_bytes_per_token"] == 3 * 160 * 4
