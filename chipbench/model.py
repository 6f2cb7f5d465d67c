"""What every model family shares, and what a family module gives.

A configuration file (HF config.json key names) names its family
(`"family": "dense"`), and `families/<family>.py` holds everything that
depends on the model's shape. The weights (`weights.py`), the reference
(`reference.py`) and the metric readers reach the model only through the
family, which travels with its record of widths (`Shape.family`). A
family module gives:

  dims(conf)               its record of widths: a frozen subclass of
                           `Shape`, defined in the family's module
  model_config(d)          the program's ModelConfig
  shared_leaves(d)         embedding, final norm and, unless the family
                           ties it, the LM head: path -> (shape, std)
  stacks(d)                program tree key (`layers`, `dense_layers`,
                           ...) -> the range of global layer numbers it
                           holds
  layer_leaves(d, layer)   one layer's leaves, path -> (shape, std); the
                           same set for every layer of a stack
  layer_kind(d, layer)     a hashable key: layers of one kind share one
                           compiled reference block
  block(d, kind, x, W, mm) one layer over a whole sequence, (S, D) ->
                           (S, D) in float32, every linear map through
                           `mm(eq, a, w)` (the reference's precision and
                           its fp8 control apply to every family alike)
  head(d, x, shared, mm)   final norm and LM head: (S, D) -> (S, vocab)
  decode_flops(d, ctx)     model FLOPs of one decoded token at context ctx
  prefill_flops(d, n)      model FLOPs of an n-token prompt (both count
                           the active experts only, where there are any)
  kernel_layers(d)         a `KernelLayer` for each layer the Pallas
                           decode-attention kernel serves
"""
from __future__ import annotations

import sys
from dataclasses import dataclass
from typing import NamedTuple, Optional


@dataclass(frozen=True)
class Shape:
    """The widths every family has; a family's record adds its own."""
    name: str
    d_model: int
    n_layers: int
    vocab: int
    norm_eps: float

    @property
    def padded_vocab(self) -> int:
        """The program pads the embedding and LM head to a multiple of
        256 rows; the pad rows are never read as tokens or logits."""
        return -(-self.vocab // 256) * 256

    @property
    def family(self):
        """The family module, the one that defined this record's type."""
        return sys.modules[type(self).__module__]


def shape_fields(conf: dict) -> dict:
    """The shared widths from a configuration file, as `Shape` fields."""
    if conf.get("torch_dtype") != "bfloat16":
        raise ValueError(f"{conf['name']}: served in bfloat16 only")
    return dict(name=conf["name"], d_model=conf["hidden_size"],
                n_layers=conf["num_hidden_layers"],
                vocab=conf["vocab_size"],
                norm_eps=float(conf["rms_norm_eps"]))


class KernelLayer(NamedTuple):
    """A layer that the Pallas decode-attention kernel serves: its query
    heads, KV heads, head width and window (None: the whole context)."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: Optional[int] = None
