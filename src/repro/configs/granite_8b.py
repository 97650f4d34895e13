"""granite-8b [dense]: 36L d_model=4096 32H (GQA kv=8) d_ff=14336
vocab=49152 — llama-arch code model with a bias on every projection.

Source: ibm-granite/granite-8b-code-base ``config.json``
(https://huggingface.co/ibm-granite/granite-8b-code-base/blob/main/config.json;
arXiv:2405.04324): ``LlamaForCausalLM``, RMSNorm eps 1e-5, SwiGLU,
``rope_theta`` 1e7, context 4096, ``attention_bias`` and ``mlp_bias`` true.
Unlike the source, the unembedding is its own matrix (MPDCompress packs it)
and embeddings are scaled by sqrt(d_model)."""

from repro.models.model import ModelConfig


def full(mpd_c: int = 8, mpd_mode: str = "packed") -> ModelConfig:
    return ModelConfig(
        name="granite-8b", n_layers=36, d_model=4096, n_heads=32, n_kv_heads=8,
        d_ff=14336, vocab=49152, norm="rms", ffn_kind="swiglu",
        use_bias=True, rope_theta=10000000.0, dtype="bfloat16",
        mpd_c=mpd_c, mpd_mode=mpd_mode,
    )


def smoke() -> ModelConfig:
    """Group-4 GQA (8 heads over 2 KV heads) with biases, as full() runs."""
    return ModelConfig(
        name="granite-smoke", n_layers=2, d_model=64, n_heads=8, n_kv_heads=2,
        d_ff=128, vocab=96, norm="rms", ffn_kind="swiglu", use_bias=True,
        rope_theta=10000000.0, mpd_c=4,
    )
