"""Config presets: all recipes validate; shapes track the reference YAMLs."""

import pytest

from photon_tpu.config import list_presets, load_preset


def test_all_presets_validate():
    names = list_presets()
    assert {"mpt-125m", "mpt-350m", "mpt-760m", "mpt-1b", "mpt-3b", "mpt-7b"} <= set(names)
    for name in names:
        cfg = load_preset(name)
        # a latent-attention preset's heads are as wide as its projections
        # make them (2,048 over 20 heads of 256), and so are those of a preset
        # that states `head_dim` (2,048 over 48 heads of 128): d_head answers
        assert cfg.model.d_head > 0, name
        assert (cfg.model.latent_attention or cfg.model.head_dim
                or cfg.model.d_model % cfg.model.n_heads == 0), name
        assert cfg.scheduler.t_max > 100


def test_125m_matches_reference_recipe():
    cfg = load_preset("mpt-125m")
    m = cfg.model
    assert (m.d_model, m.n_layers, m.n_heads, m.max_seq_len, m.vocab_size) == (768, 12, 12, 2048, 50368)
    assert cfg.optimizer.name == "adopt" and cfg.optimizer.lr == 6.0e-4
    assert cfg.train.global_batch_size == 256 and cfg.scheduler.t_max == 4800


def test_1b_matches_reference_recipe():
    cfg = load_preset("mpt-1b")
    m = cfg.model
    assert (m.d_model, m.n_layers, m.n_heads) == (2048, 24, 16)
    assert m.d_head == 128  # flash-attn-friendly head dim (reference note)
    assert m.remat  # activation checkpointing on at 1B
    assert cfg.optimizer.name == "adamw"


def test_preset_overrides_merge():
    cfg = load_preset("mpt-125m", fl={"n_rounds": 10}, seed=3)
    assert cfg.fl.n_rounds == 10 and cfg.seed == 3
    assert cfg.model.d_model == 768


def test_unknown_preset_raises():
    with pytest.raises(ValueError):
        load_preset("mpt-999t")
