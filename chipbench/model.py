"""A configuration file (HF config.json key names) as the program's
ModelConfig, and the sizes the counts and the reference need."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    """The widths of a dense decoder, from a configuration file."""
    name: str
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float

    @property
    def padded_vocab(self) -> int:
        """The program pads the embedding and LM head to a multiple of
        256 rows; the pad rows are never read as tokens or logits."""
        return -(-self.vocab // 256) * 256


def dims(conf: dict) -> Dims:
    if conf.get("tie_word_embeddings"):
        raise ValueError(f"{conf['name']}: tied embeddings are not supported")
    if conf.get("torch_dtype") != "bfloat16":
        raise ValueError(f"{conf['name']}: served in bfloat16 only")
    return Dims(
        name=conf["name"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim",
                          conf["hidden_size"] // conf["num_attention_heads"]),
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]))


def model_config(d: Dims):
    """The program's ModelConfig for these widths (imports the program)."""
    from repro.configs.base import AttnKind, Family, ModelConfig
    return ModelConfig(
        name=d.name, family=Family.DENSE, n_layers=d.n_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_kv_heads,
        d_ff=d.d_ff, vocab_size=d.vocab, head_dim=d.head_dim,
        attn_kind=AttnKind.FULL, rope_theta=d.rope_theta,
        norm_eps=d.norm_eps)
