"""Plain reference of granite-3-2b's block: pre-norm grouped-query
attention with rotary positions, then a SwiGLU MLP."""
from perfbench.reference import (Leaf, attention, attention_specs, lm_specs,
                                 mlp_specs, rms_norm, swiglu)


def param_specs(c: dict) -> dict:
    d = c["hidden_size"]
    return lm_specs(c, {"attn": attention_specs(c), "ln1": Leaf((d,), "ones"),
                        "ln2": Leaf((d,), "ones"), "mlp": mlp_specs(c)})


def block(p: dict, x, c: dict, pr):
    eps, res = c["rms_norm_eps"], c["residual_multiplier"]
    x = x + res * attention(p["attn"], rms_norm(x, p["ln1"], eps), c, pr)
    return x + res * swiglu(p["mlp"], rms_norm(x, p["ln2"], eps), pr)
