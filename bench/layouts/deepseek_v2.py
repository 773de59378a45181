"""Layout of a DeepSeek-V2 decoder (arXiv:2405.04434; DeepSeek-V2-Lite): what
the harness does with such a model besides the float32 reference
(``bench/reference/deepseek_v2.py``).  The module contract is
``bench/layouts/llama.py``'s.

The block: multi-head latent attention with no query compression
(``q_lora_rank`` null) and YaRN rotary scaling; ``first_k_dense_replace``
layers end in a SwiGLU MLP, the others in ``n_routed_experts`` routed experts
(softmax gates, top ``num_experts_per_tok``, renormalised only if
``norm_topk_prob``) beside ``n_shared_experts`` shared ones.

Its layer weights, each stack on a leading axis of its layers:
  both:  ln1 [L, d], wq [L, d, H, nope+rope], wdkv [L, d, kvl+rope],
         kv_norm [L, kvl], wuk [L, kvl, H, nope], wuv [L, kvl, H, v],
         wo [L, H, v, d], ln2 [L, d]
  dense: wg/wu [L0, d, F], wd [L0, F, d]
  moe:   router [L1, d, E], ewg/ewu [L1, E, d, f], ewd [L1, E, f, d],
         swg/swu [L1, d, S*f], swd [L1, S*f, d]
and the program holds them as two groups of one stacked block each, the
dense layers first.

Every matrix is drawn from N(0, 1/fan_in), as in ``bench/layouts/llama.py``:
each sublayer writes about a unit of RMS into the residual stream beside the
unit-scale embedding (``bench/weights.py``).  At these widths and seven
layers the final residual keeps a cosine of about 0.45 with the current
token's embedding, so the planted next-token map holds on every seed, with
no scaling of the residual writers.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from bench.weights import normal

KEYS = 20

# published key -> the program's ModelConfig attribute
_PROGRAM_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
                 "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
                 "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
                 "n_routed_experts": "n_experts", "n_shared_experts": "n_shared_experts",
                 "num_experts_per_tok": "moe_top_k", "first_k_dense_replace": "first_k_dense",
                 "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "nope_head_dim",
                 "qk_rope_head_dim": "rope_head_dim", "v_head_dim": "v_head_dim",
                 "vocab_size": "vocab_size", "rope_theta": "rope_theta",
                 "rms_norm_eps": "norm_eps", "norm_topk_prob": "norm_topk_prob"}
# published rope_scaling key -> the program's YarnScaling attribute
_YARN_KEYS = {"factor": "factor",
              "original_max_position_embeddings": "original_max_position_embeddings",
              "beta_fast": "beta_fast", "beta_slow": "beta_slow", "mscale": "mscale",
              "mscale_all_dim": "mscale_all_dim"}
# published settings the program implements only at these values
_FIXED = {"scoring_func": "softmax", "topk_method": "greedy", "n_group": 1, "topk_group": 1,
          "routed_scaling_factor": 1, "moe_layer_freq": 1, "attention_bias": False,
          "hidden_act": "silu", "tie_word_embeddings": False, "q_lora_rank": None}
# plain name -> the program's MLA parameter
_ATTN = {"wq": "w_q", "wdkv": "w_dkv", "kv_norm": "kv_norm", "wuk": "w_uk", "wuv": "w_uv",
         "wo": "wo"}
_MLP = ("wg", "wu", "wd")
# plain name -> path in the program's MoE block
_MOE = {"router": ("router",), "ewg": ("wg",), "ewu": ("wu",), "ewd": ("wd",),
        "swg": ("shared", "wg"), "swu": ("shared", "wu"), "swd": ("shared", "wd")}


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d_model: int
    n_dense: int
    n_moe: int
    n_heads: int
    nope: int
    rope: int
    v_dim: int
    kv_lora: int
    d_ff: int
    moe_d_ff: int
    n_experts: int
    n_shared: int
    top_k: int


def dims(hf: dict) -> Dims:
    k = hf["first_k_dense_replace"]
    return Dims(vocab=hf["vocab_size"], d_model=hf["hidden_size"], n_dense=k,
                n_moe=hf["num_hidden_layers"] - k, n_heads=hf["num_attention_heads"],
                nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
                v_dim=hf["v_head_dim"], kv_lora=hf["kv_lora_rank"],
                d_ff=hf["intermediate_size"], moe_d_ff=hf["moe_intermediate_size"],
                n_experts=hf["n_routed_experts"], n_shared=hf["n_shared_experts"],
                top_k=hf["num_experts_per_tok"])


def same_model(label: str, hf: dict, mc) -> None:
    """The program's ModelConfig must be the configuration file's model:
    every published number the program runs, the rotary scaling and the
    routing included."""

    def differ(what, stated, run):
        raise ValueError(f"{label}: the configuration states {what}={stated!r}, the program "
                         f"would run {run!r}")

    for k, attr in _PROGRAM_KEYS.items():
        if float(hf[k]) != float(getattr(mc, attr)):
            differ(k, hf[k], f"{attr}={getattr(mc, attr)}")
    for k, v in _FIXED.items():
        if hf.get(k, v) != v:
            differ(k, hf[k], f"{k}={v}")
    if mc.attn_kind != "mla" or mc.q_lora_rank or tuple(mc.block_pattern) != ("moe",):
        differ("the block", "latent attention without query compression, then MoE layers",
               f"attn_kind={mc.attn_kind}, q_lora_rank={mc.q_lora_rank}, "
               f"block_pattern={mc.block_pattern}")
    rope = hf.get("rope_scaling") or {}
    if rope.get("type", "yarn") != "yarn":
        differ("rope_scaling", rope, "YaRN or none")
    if bool(rope) != (mc.yarn is not None):
        differ("rope_scaling", rope, f"yarn={mc.yarn}")
    for k, attr in _YARN_KEYS.items() if rope else ():
        if float(rope[k]) != float(getattr(mc.yarn, attr)):
            differ(f"rope_scaling.{k}", rope[k], f"yarn.{attr}={getattr(mc.yarn, attr)}")


def _attn(keys, n, dm: Dims, dt) -> dict:
    d, H, kvl = dm.d_model, dm.n_heads, dm.kv_lora
    return {"ln1": jnp.ones((n, d), dt), "ln2": jnp.ones((n, d), dt),
            "kv_norm": jnp.ones((n, kvl), dt),
            "wq": normal(keys[0], (n, d, H, dm.nope + dm.rope), d ** -0.5, dt),
            "wdkv": normal(keys[1], (n, d, kvl + dm.rope), d ** -0.5, dt),
            "wuk": normal(keys[2], (n, kvl, H, dm.nope), kvl ** -0.5, dt),
            "wuv": normal(keys[3], (n, kvl, H, dm.v_dim), kvl ** -0.5, dt),
            "wo": normal(keys[4], (n, H, dm.v_dim, d), (H * dm.v_dim) ** -0.5, dt)}


def layer_weights(keys, dm: Dims, dt) -> dict:
    d, ff, f, E = dm.d_model, dm.d_ff, dm.moe_d_ff, dm.n_experts
    L0, L1, sf = dm.n_dense, dm.n_moe, dm.n_shared * dm.moe_d_ff
    dense = {**_attn(keys[0:5], L0, dm, dt),
             "wg": normal(keys[5], (L0, d, ff), d ** -0.5, dt),
             "wu": normal(keys[6], (L0, d, ff), d ** -0.5, dt),
             "wd": normal(keys[7], (L0, ff, d), ff ** -0.5, dt)}
    moe = {**_attn(keys[8:13], L1, dm, dt),
           "router": normal(keys[13], (L1, d, E), d ** -0.5, dt),
           "ewg": normal(keys[14], (L1, E, d, f), d ** -0.5, dt),
           "ewu": normal(keys[15], (L1, E, d, f), d ** -0.5, dt),
           "ewd": normal(keys[16], (L1, E, f, d), f ** -0.5, dt),
           "swg": normal(keys[17], (L1, d, sf), d ** -0.5, dt),
           "swu": normal(keys[18], (L1, d, sf), d ** -0.5, dt),
           "swd": normal(keys[19], (L1, sf, d), sf ** -0.5, dt)}
    return {"dense": dense, "moe": moe}


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
    """The program's parameter tree over the benchmark's arrays (no copy)."""
    from repro.sharding import Param

    groups = []
    for kind, blk in _blocks(like["groups"]):
        L = plain["layers"][kind]
        b = {"ln1": Param(L["ln1"], blk["ln1"].axes), "ln2": Param(L["ln2"], blk["ln2"].axes),
             "attn": {p: Param(L[k], blk["attn"][p].axes) for k, p in _ATTN.items()}}
        if kind == "dense":
            b["mlp"] = {k: Param(L[k], blk["mlp"][k].axes) for k in _MLP}
        else:
            b["mlp"] = {"shared": {}}
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
        s = {"ln1": g["ln1"], "ln2": g["ln2"], **{k: g["attn"][p] for k, p in _ATTN.items()}}
        if kind == "dense":
            s.update({k: g["mlp"][k] for k in _MLP})
        else:
            s.update({name: _get(g["mlp"], path) for name, path in _MOE.items()})
        layers[kind] = s
    return {"embed": sh["embed"], "final_norm": sh["final_norm"], "lm_head": sh["lm_head"],
            "layers": layers}


@dataclasses.dataclass(frozen=True)
class Counts:
    """Operations and bytes that one call needs, from shapes alone.

    A call runs ``rows`` sequences of ``tokens`` new tokens each; each row
    attends ``kv_rows`` earlier positions from the latent cache.  The weights
    are split evenly over ``tp`` chips (the numbers are per chip).  Counted:

      FLOPs   2 per weight per token for every matrix a token uses: latent
              attention in the absorbed form (``wq``, ``wdkv``, ``wuk`` folded
              into the query, ``wuv`` after the weighted sum, ``wo``), the
              dense MLP, the router, the shared experts, ``top_k`` routed
              experts, the output head; and 2 * heads * (2 * kv_lora + rope)
              per attended position per token per layer (scores against the
              latent and the rope key, the latent weighted sum)
      bytes   every weight but the routed experts' once; of the routed
              experts, per MoE layer, the expected number of distinct experts
              that ``rows * tokens`` tokens reach when each picks ``top_k`` of
              ``n_experts`` uniformly, E * (1 - (1 - k/E)^n); the embedding
              rows looked up; the latent cache rows read, (kv_lora + rope)
              per position per layer, and those the new tokens write

    Norm scales are read but counted with the weights; logits never leave
    the chip.
    """

    dm: Dims
    bytes_per: int = 2  # bfloat16 weights and cache

    @property
    def _attn(self) -> int:
        dm = self.dm
        return (dm.d_model * dm.n_heads * (dm.nope + dm.rope) + dm.d_model * (dm.kv_lora + dm.rope)
                + dm.kv_lora * dm.n_heads * (dm.nope + dm.v_dim) + dm.n_heads * dm.v_dim * dm.d_model)

    @property
    def _expert(self) -> int:
        return 3 * self.dm.d_model * self.dm.moe_d_ff

    @property
    def _norms(self) -> int:
        return 2 * self.dm.d_model + self.dm.kv_lora

    @property
    def _unrouted(self) -> int:
        """Weights of the layers but the routed experts', head and final norm."""
        dm = self.dm
        dense = self._attn + 3 * dm.d_model * dm.d_ff + self._norms
        moe = self._attn + dm.d_model * dm.n_experts + dm.n_shared * self._expert + self._norms
        return dm.n_dense * dense + dm.n_moe * moe + dm.vocab * dm.d_model + dm.d_model

    @property
    def params(self) -> int:
        """Every parameter, the embedding included."""
        dm = self.dm
        return self._unrouted + dm.n_moe * dm.n_experts * self._expert + dm.vocab * dm.d_model

    def experts_reached(self, n_tokens: int) -> float:
        """Expected distinct routed experts per MoE layer that ``n_tokens``
        tokens reach, each choosing ``top_k`` of ``n_experts`` uniformly."""
        E, k = self.dm.n_experts, self.dm.top_k
        return E * (1.0 - (1.0 - k / E) ** n_tokens)

    def call(self, rows: int, tokens: int, kv_rows: float, tp: int = 1) -> tuple[float, float]:
        """(FLOPs, bytes) per chip of one call."""
        dm, n = self.dm, rows * tokens
        layers = dm.n_dense + dm.n_moe
        used = (layers * self._attn + dm.n_dense * 3 * dm.d_model * dm.d_ff
                + dm.n_moe * (dm.d_model * dm.n_experts + (dm.n_shared + dm.top_k) * self._expert)
                + dm.vocab * dm.d_model)
        attn = 2 * layers * dm.n_heads * (2 * dm.kv_lora + dm.rope) * (kv_rows + tokens)
        flops = (2 * used + attn) * n
        routed = dm.n_moe * self.experts_reached(n) * self._expert
        latent_row = layers * (dm.kv_lora + dm.rope) * self.bytes_per
        nbytes = ((self._unrouted + routed) * self.bytes_per + n * dm.d_model * self.bytes_per
                  + rows * kv_rows * latent_row + n * latent_row)
        return flops / tp, nbytes / tp


def roofline(hf: dict) -> Counts:
    return Counts(dims(hf))

