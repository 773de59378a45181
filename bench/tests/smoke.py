"""A benchmark root at smoke size for tests on the CPU: the program's smoke
shapes of the same pair, short mixes, and the real metric readers, layout
modules and references.  The
harness finds everything by name in it, as in the repository's own root."""

from __future__ import annotations

import contextlib
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
# the limit the chip configuration states
LIMIT = json.loads((BENCH / "configs" / "dscoder33b-l6_1.3b.json").read_text())["check"]

SMOKE_CONFIG = {
    "source": "https://huggingface.co/deepseek-ai/deepseek-coder-33b-base",
    "deployment": "test: the program's smoke shapes of the DeepSeek-Coder pair",
    "chips": 1,
    "hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 8,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
    "draft": {"hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 256,
              "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-5,
              "tie_word_embeddings": False},
    "reduced": ["num_hidden_layers"],
    "program": {"target": "deepseek-coder-33b", "draft": "deepseek-coder-1.3b", "smoke": True,
                "dtype": "bfloat16", "n_target": 1, "n_draft": 0,
                "bs": 8, "w": 4, "c": 2, "d": 2, "async_rounds": True},
    "assumed": {"draft_agreement": 0.8, "free_share": 0.1, "logit_scale": 4.0},
    "reference": "llama",
    "check": LIMIT,
}

MIXES = {
    "single-smoke": {"loop": "closed", "clients": 1, "slots": 1, "prompt_buckets": [16, 24],
                     "prompt_weights": [0.5, 0.5], "output": {"kind": "fixed", "tokens": 32},
                     "queue": 16, "shape_seed": 1, "check_requests": 3},
    "chat-smoke": {"loop": "open", "slots": 2, "rate_rps": 8.0, "cv": 2.0,
                   "prompt_buckets": [8, 16], "prompt_weights": [0.5, 0.5],
                   "output": {"kind": "lognormal", "median": 8, "sigma": 0.8, "min": 4, "max": 24},
                   "shape_seed": 2, "check_requests": 4},
}


def make_root(tmp: Path, bench: dict | None = None) -> Path:
    """Write a benchmark root under ``tmp``: BENCHMARK.json (``bench``, or the
    repository's metrics with two smoke cells) and the files it names."""
    root = Path(tmp)
    (root / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "layouts", "reference"):
        shutil.copytree(BENCH / sub, root / "bench" / sub, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "configs" / "smoke.json").write_text(json.dumps(SMOKE_CONFIG))
    for name, mix in MIXES.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    if bench is None:
        real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
        cells = {"single": "smoke.single", "chat": "smoke.chat"}
        real_cells = {w["traffic"]: w["name"] for w in real["workloads"]}

        def rename(names):
            return [cells[t] for t, n in real_cells.items() if n in names and t in cells]

        for m in real["end_to_end"] + real["per_layer"]:
            if "workloads" in m:
                m["workloads"] = rename(m["workloads"])
        real["configs"] = [{"name": "smoke", "source": SMOKE_CONFIG["source"],
                            "file": "bench/configs/smoke.json", "reduced": ["num_hidden_layers"],
                            "why": "test"}]
        real["workloads"] = [
            {"name": "smoke.single", "config": "smoke", "traffic": "single-smoke", "chips": 1,
             "why": "test"},
            {"name": "smoke.chat", "config": "smoke", "traffic": "chat-smoke", "chips": 1,
             "why": "test"}]
        bench = real
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@contextlib.contextmanager
def jax_config_kept():
    """Restore the JAX options a harness run sets (the compile cache) after a
    test, so no other test in the worker sees them."""
    import jax

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        yield
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
