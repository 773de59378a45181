"""deepseek-v2-lite-draft — a dense draft for deepseek-v2-lite: the
DeepSeek-Coder-1.3B block (hf:deepseek-ai/deepseek-coder-1.3b-base) on the
target's 102,400-token vocabulary, as a draft distilled on the target's
tokenizer would be.  No public dense model that small shares that
vocabulary; the block is the published one."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-draft",
    n_layers=24,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=5504,
    vocab_size=102400,
    rope_theta=1e5,
    norm_eps=1e-6,
    family="dense",
    source="hf:deepseek-ai/deepseek-coder-1.3b-base, vocabulary of arXiv:2405.04434",
)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-draft-smoke",
        n_layers=2,
        d_model=32,
        n_heads=4,
        n_kv_heads=4,
        d_ff=64,
        vocab_size=256,
        norm_eps=1e-6,
        family="dense",
    )
