"""Layout of a Llama-architecture decoder (DeepSeek-Coder): what the harness
does with such a model besides the float32 reference
(``bench/reference/llama.py``).

A configuration names its layout module in ``reference`` (the draft may
name its own in ``draft.reference``); ``spec.load_layout`` finds it under
``bench/layouts/``.  Every layout module supplies:

  KEYS                              how many seeded keys ``layer_weights`` takes
  dims(hf)                          the sizes, from the configuration's model section
  same_model(label, hf, mc)         raise unless the program's ModelConfig is that model
  layer_weights(keys, dims, dtype)  the seeded layer weights, in the reference's layout
  program_tree(plain, like)         the program's parameter tree over those arrays
  plain_shardings(shardings)        the program's shardings, in the reference's layout
  roofline(hf)                      an object with ``params`` and ``call(rows, tokens,
                                    kv_rows, tp)`` (``bench/roofline.py``)

Here the layer weights are stacked on a leading axis of ``n_layers``:
  ln1 [L, d], wq [L, d, Hq, hd], wk/wv [L, d, Hkv, hd], wo [L, Hq, hd, d],
  ln2 [L, d], wg/wu [L, d, F], wd [L, F, d]
and the program holds them as one group of one stacked dense block.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from bench.roofline import Decoder
from bench.weights import normal

KEYS = 7

# published key -> the program's ModelConfig attribute
_PROGRAM_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
                 "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
                 "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                 "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}
_ATTN = ("wq", "wk", "wv", "wo")
_MLP = ("wg", "wu", "wd")


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int


def dims(hf: dict) -> Dims:
    return Dims(vocab=hf["vocab_size"], d_model=hf["hidden_size"],
                n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
                n_kv_heads=hf["num_key_value_heads"],
                head_dim=hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
                d_ff=hf["intermediate_size"])


def same_model(label: str, hf: dict, mc) -> None:
    """The program's ModelConfig must be the configuration file's model, as
    run (``runs_as`` applied)."""
    for k, attr in _PROGRAM_KEYS.items():
        if float(hf[k]) != float(getattr(mc, attr)):
            raise ValueError(f"{label}: the configuration states {k}={hf[k]}, the program "
                             f"would run {attr}={getattr(mc, attr)}")
    if hf.get("rope_scaling") or hf.get("tie_word_embeddings"):
        raise ValueError(f"{label}: the program has no rotary scaling and no tied head")


def layer_weights(keys, dims: Dims, dt) -> dict:
    L, d = dims.n_layers, dims.d_model
    hq, hkv, hd, ff = dims.n_heads, dims.n_kv_heads, dims.head_dim, dims.d_ff
    return {
        "ln1": jnp.ones((L, d), dt),
        "wq": normal(keys[0], (L, d, hq, hd), d ** -0.5, dt),
        "wk": normal(keys[1], (L, d, hkv, hd), d ** -0.5, dt),
        "wv": normal(keys[2], (L, d, hkv, hd), d ** -0.5, dt),
        "wo": normal(keys[3], (L, hq, hd, d), (hq * hd) ** -0.5, dt),
        "ln2": jnp.ones((L, d), dt),
        "wg": normal(keys[4], (L, d, ff), d ** -0.5, dt),
        "wu": normal(keys[5], (L, d, ff), d ** -0.5, dt),
        "wd": normal(keys[6], (L, ff, d), ff ** -0.5, dt),
    }


def program_tree(plain: dict, like) -> dict:
    """The program's parameter tree over the benchmark's arrays (no copy)."""
    from repro.sharding import Param

    blk = like["groups"][0][0]
    L = plain["layers"]
    block = {"ln1": Param(L["ln1"], blk["ln1"].axes),
             "attn": {k: Param(L[k], blk["attn"][k].axes) for k in _ATTN},
             "ln2": Param(L["ln2"], blk["ln2"].axes),
             "mlp": {k: Param(L[k], blk["mlp"][k].axes) for k in _MLP}}
    return {"embed": Param(plain["embed"], like["embed"].axes),
            "final_norm": Param(plain["final_norm"], like["final_norm"].axes),
            "lm_head": Param(plain["lm_head"], like["lm_head"].axes),
            "groups": [(block,)], "shared_attn": None}


def plain_shardings(sh) -> dict:
    g = sh["groups"][0][0]
    return {"embed": sh["embed"], "final_norm": sh["final_norm"], "lm_head": sh["lm_head"],
            "layers": {"ln1": g["ln1"], "ln2": g["ln2"],
                       **{k: g["attn"][k] for k in _ATTN}, **{k: g["mlp"][k] for k in _MLP}}}


def roofline(hf: dict) -> Decoder:
    return Decoder.of(hf)
