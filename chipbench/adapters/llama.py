"""The program under test, fed the benchmark's Llama-style weights.

Maps a configuration file onto the program's ``ModelConfig`` and the
benchmark's weights (:mod:`chipbench.reference.llama`) onto the
program's parameter tree.  The arrays are shared, not copied.
"""

from __future__ import annotations

from repro.configs import ModelConfig

from chipbench.reference.llama import Dims


def model_config(name: str, dims: Dims) -> ModelConfig:
    """Every size of the configuration file, as the program names it."""
    return ModelConfig(
        name=name, family="dense", n_layers=dims.layers, d_model=dims.d,
        n_heads=dims.heads, n_kv_heads=dims.kv_heads, d_ff=dims.ff,
        vocab_size=dims.vocab, head_dim=dims.head_dim, mlp_type="swiglu",
        rope_theta=dims.theta, norm_eps=dims.eps, tie_embeddings=False)


def program_params(w: dict) -> dict:
    """The program's tree: ``wi`` is SwiGLU's up projection, ``wg`` its
    gate (``mlp = wo(silu(wg x) * wi x)``)."""
    lw = w["layers"]
    return {
        "embed": {"tokens": w["embed"], "lm_head": w["lm_head"]},
        "final_norm": {"scale": w["final_norm"]},
        "layers": {
            "attn_norm": {"scale": lw["attn_norm"]},
            "attn": {name: {"w": lw[name]} for name in ("wq", "wk", "wv",
                                                          "wo")},
            "mlp_norm": {"scale": lw["mlp_norm"]},
            "mlp": {"wi": {"w": lw["w_up"]}, "wg": {"w": lw["w_gate"]},
                    "wo": {"w": lw["w_down"]}},
        },
    }
