"""Layout of a DeepSeek-MoE decoder (arXiv:2401.06066), for the harness's
tests: ``first_k_dense_replace`` dense layers, then layers whose MLP is
``n_routed_experts`` routed experts (softmax gates over all of them, the
top ``num_experts_per_tok`` renormalised) beside ``n_shared_experts`` shared
ones.  It lives only in a test's benchmark root, as
``bench/layouts/dsmoe.py`` beside ``bench/reference/dsmoe.py``: a model of
another architecture than the repository's is new files only.

Its layer weights, each stack on a leading axis of its layers:
  dense: ln1, wq, wk, wv, wo, ln2, wg [L0, d, F], wu, wd
  moe:   ln1, wq, wk, wv, wo, ln2, router [L1, d, E],
         ewg/ewu [L1, E, d, f], ewd [L1, E, f, d],
         swg/swu [L1, d, S*f], swd [L1, S*f, d]
and the program holds them as two groups of one stacked block each.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from bench.weights import normal

KEYS = 18

_PROGRAM_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
                 "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
                 "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
                 "n_routed_experts": "n_experts", "n_shared_experts": "n_shared_experts",
                 "num_experts_per_tok": "moe_top_k", "first_k_dense_replace": "first_k_dense",
                 "vocab_size": "vocab_size", "rope_theta": "rope_theta",
                 "rms_norm_eps": "norm_eps"}
_ATTN = ("wq", "wk", "wv", "wo")
_MLP = ("wg", "wu", "wd")


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d_model: int
    n_dense: int
    n_moe: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    moe_d_ff: int
    n_experts: int
    n_shared: int
    top_k: int


def dims(hf: dict) -> Dims:
    k = hf["first_k_dense_replace"]
    return Dims(vocab=hf["vocab_size"], d_model=hf["hidden_size"], n_dense=k,
                n_moe=hf["num_hidden_layers"] - k, n_heads=hf["num_attention_heads"],
                n_kv_heads=hf["num_key_value_heads"],
                head_dim=hf["hidden_size"] // hf["num_attention_heads"],
                d_ff=hf["intermediate_size"], moe_d_ff=hf["moe_intermediate_size"],
                n_experts=hf["n_routed_experts"], n_shared=hf["n_shared_experts"],
                top_k=hf["num_experts_per_tok"])


def same_model(label: str, hf: dict, mc) -> None:
    for k, attr in _PROGRAM_KEYS.items():
        if float(hf[k]) != float(getattr(mc, attr)):
            raise ValueError(f"{label}: the configuration states {k}={hf[k]}, the program "
                             f"would run {attr}={getattr(mc, attr)}")
    if not hf["norm_topk_prob"] or hf["scoring_func"] != "softmax":
        raise ValueError(f"{label}: the program renormalises softmax gates over the top k")
    if mc.capacity_factor * mc.moe_top_k < mc.n_experts:
        raise ValueError(f"{label}: the program's experts would drop tokens; the reference "
                         f"is dropless")
    if hf.get("rope_scaling") or hf.get("tie_word_embeddings") or mc.qkv_bias:
        raise ValueError(f"{label}: no rotary scaling, tied head or attention bias here")


def _attn(keys, n, dm: Dims, dt) -> dict:
    d, hq, hkv, hd = dm.d_model, dm.n_heads, dm.n_kv_heads, dm.head_dim
    return {"ln1": jnp.ones((n, d), dt), "ln2": jnp.ones((n, d), dt),
            "wq": normal(keys[0], (n, d, hq, hd), d ** -0.5, dt),
            "wk": normal(keys[1], (n, d, hkv, hd), d ** -0.5, dt),
            "wv": normal(keys[2], (n, d, hkv, hd), d ** -0.5, dt),
            "wo": normal(keys[3], (n, hq, hd, d), (hq * hd) ** -0.5, dt)}


def layer_weights(keys, dm: Dims, dt) -> dict:
    d, ff, f, E = dm.d_model, dm.d_ff, dm.moe_d_ff, dm.n_experts
    L0, L1, sf = dm.n_dense, dm.n_moe, dm.n_shared * dm.moe_d_ff
    dense = {**_attn(keys[0:4], L0, dm, dt),
             "wg": normal(keys[4], (L0, d, ff), d ** -0.5, dt),
             "wu": normal(keys[5], (L0, d, ff), d ** -0.5, dt),
             "wd": normal(keys[6], (L0, ff, d), ff ** -0.5, dt)}
    moe = {**_attn(keys[7:11], L1, dm, dt),
           "router": normal(keys[11], (L1, d, E), d ** -0.5, dt),
           "ewg": normal(keys[12], (L1, E, d, f), d ** -0.5, dt),
           "ewu": normal(keys[13], (L1, E, d, f), d ** -0.5, dt),
           "ewd": normal(keys[14], (L1, E, f, d), f ** -0.5, dt),
           "swg": normal(keys[15], (L1, d, sf), d ** -0.5, dt),
           "swu": normal(keys[16], (L1, d, sf), d ** -0.5, dt),
           "swd": normal(keys[17], (L1, sf, d), sf ** -0.5, dt)}
    return {"dense": dense, "moe": moe}


# plain name -> path in the program's MoE block
_MOE = {"router": ("router",), "ewg": ("wg",), "ewu": ("wu",), "ewd": ("wd",),
        "swg": ("shared", "wg"), "swu": ("shared", "wu"), "swd": ("shared", "wd")}


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _blocks(like_groups):
    """(kind, the program's block) of each group, dense layers first."""
    out = [("moe" if "router" in blk["mlp"] else "dense", blk) for (blk,) in like_groups]
    if [k for k, _ in out] != ["dense", "moe"]:
        raise ValueError(f"the program's groups are {[k for k, _ in out]}, not dense then moe")
    return out


def program_tree(plain: dict, like) -> dict:
    from repro.sharding import Param

    groups = []
    for kind, blk in _blocks(like["groups"]):
        L = plain["layers"][kind]
        b = {"ln1": Param(L["ln1"], blk["ln1"].axes), "ln2": Param(L["ln2"], blk["ln2"].axes),
             "attn": {k: Param(L[k], blk["attn"][k].axes) for k in _ATTN}}
        if kind == "dense":
            b["mlp"] = {k: Param(L[k], blk["mlp"][k].axes) for k in _MLP}
        else:
            b["mlp"] = {"router": None, "wg": None, "wu": None, "wd": None,
                        "shared": {"wg": None, "wu": None, "wd": None}}
            for name, path in _MOE.items():
                _get(b["mlp"], path[:-1])[path[-1]] = Param(L[name], _get(blk["mlp"], path).axes)
        groups.append((b,))
    return {"embed": Param(plain["embed"], like["embed"].axes),
            "final_norm": Param(plain["final_norm"], like["final_norm"].axes),
            "lm_head": Param(plain["lm_head"], like["lm_head"].axes),
            "groups": groups, "shared_attn": None}


def plain_shardings(sh) -> dict:
    layers = {}
    for kind, g in _blocks(sh["groups"]):
        s = {"ln1": g["ln1"], "ln2": g["ln2"], **{k: g["attn"][k] for k in _ATTN}}
        if kind == "dense":
            s.update({k: g["mlp"][k] for k in _MLP})
        else:
            s.update({name: _get(g["mlp"], path) for name, path in _MOE.items()})
        layers[kind] = s
    return {"embed": sh["embed"], "final_norm": sh["final_norm"], "lm_head": sh["lm_head"],
            "layers": layers}


@dataclasses.dataclass(frozen=True)
class Counts:
    """Operations and bytes of one call: the experts' bytes as if every
    expert were read, their operations for the experts each token uses."""

    dm: Dims
    bytes_per: int = 2

    def _attn(self) -> int:
        dm = self.dm
        return 2 * dm.d_model * dm.head_dim * (dm.n_heads + dm.n_kv_heads)

    @property
    def params(self) -> int:
        dm = self.dm
        d, f = dm.d_model, dm.moe_d_ff
        dense = self._attn() + 3 * d * dm.d_ff + 2 * d
        moe = (self._attn() + d * dm.n_experts + 3 * d * f * (dm.n_experts + dm.n_shared)
               + 2 * d)
        return dm.n_dense * dense + dm.n_moe * moe + 2 * dm.vocab * d + d

    def call(self, rows: int, tokens: int, kv_rows: float, tp: int = 1):
        dm = self.dm
        d, f, n = dm.d_model, dm.moe_d_ff, rows * tokens
        used = (self._attn() + 3 * d * dm.d_ff) * dm.n_dense + dm.n_moe * (
            self._attn() + d * dm.n_experts + 3 * d * f * (dm.top_k + dm.n_shared))
        layers = dm.n_dense + dm.n_moe
        attn = 4 * layers * dm.n_heads * dm.head_dim * (kv_rows + tokens)
        flops = (2 * (used + dm.vocab * d) + attn) * n
        kv_row = 2 * layers * dm.n_kv_heads * dm.head_dim * self.bytes_per
        nbytes = ((self.params - dm.vocab * d) * self.bytes_per + n * d * self.bytes_per
                  + rows * kv_rows * kv_row + n * kv_row)
        return flops / tp, nbytes / tp


def roofline(hf: dict) -> Counts:
    return Counts(dims(hf))
