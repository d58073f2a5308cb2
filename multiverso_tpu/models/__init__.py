"""Model families: word2vec (skip-gram/CBOW), logistic regression/FTRL,
the transformer LM parallelism showcase, and the serve-only LongCat-Flash,
DeepSeek-V3-architecture and ``bailing_hybrid`` (Ling) shares; :func:`from_config` builds the LMs
from a configuration dict."""

from . import deepseek_v3, ling, longcat
from .deepseek_v3 import DeepSeekV3Config, DeepSeekV3LM
from .ling import LingConfig, LingLM
from .logreg import FTRLLogReg, LogReg, LogRegConfig, SparseLogReg
from .longcat import LongCatConfig, LongCatLM
from .transformer import TransformerConfig, TransformerLM
from .word2vec import (HuffmanCodes, Word2Vec, Word2VecConfig,
                       build_huffman, build_unigram_alias)


def from_config(cfg: dict, seed: int, **overrides):
    """The model a configuration file describes (``cfg["model"]``),
    weights from ``seed``: the one constructor a driver needs, so that
    it names no model. ``overrides`` are constructor settings that are
    no part of the configuration (``attention="reference"`` off the
    chip); a kind that takes none refuses them."""
    kind = cfg.get("model")
    if kind == "transformer_lm":
        import jax.numpy as jnp

        return TransformerLM(TransformerConfig(
            vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
            n_heads=cfg["n_head"], n_layers=cfg["n_layer"],
            d_ff=cfg["n_inner"], max_seq=cfg["n_positions"],
            dtype=jnp.dtype(cfg["dtype"]),
            learning_rate=cfg["learning_rate"], momentum=cfg["momentum"],
            seed=int(seed), **overrides))
    if kind in ("longcat_flash", "deepseek_v3", "bailing_hybrid"):
        if overrides:
            raise TypeError(f"from_config: a {kind!r} model takes no "
                            f"overrides, got {sorted(overrides)}")
        if kind == "longcat_flash":
            return LongCatLM(longcat.config_from_dict(cfg, seed))
        if kind == "bailing_hybrid":
            return LingLM(ling.config_from_dict(cfg, seed))
        return DeepSeekV3LM(deepseek_v3.config_from_dict(cfg, seed))
    raise ValueError(f"from_config: no model of kind {kind!r}")


__all__ = [
    "from_config",
    "DeepSeekV3Config",
    "DeepSeekV3LM",
    "LingConfig",
    "LingLM",
    "LongCatConfig",
    "LongCatLM",
    "FTRLLogReg",
    "LogReg",
    "LogRegConfig",
    "SparseLogReg",
    "TransformerConfig",
    "TransformerLM",
    "HuffmanCodes",
    "Word2Vec",
    "Word2VecConfig",
    "build_huffman",
    "build_unigram_alias",
]
