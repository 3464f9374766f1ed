"""Plain reference of hymba-1.5b's block as this benchmark states it:
attention and Mamba-1 heads read the same normed input in parallel, each
branch is RMS-normed and the two are averaged into the residual, then a
SwiGLU MLP."""
from perfbench.reference import (Leaf, attention, attention_specs, lm_specs,
                                 mamba, mamba_specs, mlp_specs, rms_norm,
                                 swiglu)


def param_specs(c: dict) -> dict:
    d = c["hidden_size"]
    one = Leaf((d,), "ones")
    return lm_specs(c, {"attn": attention_specs(c), "ln1": one, "ln2": one,
                        "mlp": mlp_specs(c), "norm_attn": one,
                        "norm_ssm": one, "ssm": mamba_specs(c)})


def block(p: dict, x, c: dict, pr):
    eps, res = c["rms_norm_eps"], c["residual_multiplier"]
    xn = rms_norm(x, p["ln1"], eps)
    a = rms_norm(attention(p["attn"], xn, c, pr), p["norm_attn"], eps)
    s = rms_norm(mamba(p["ssm"], xn, c, pr), p["norm_ssm"], eps)
    x = x + res * 0.5 * (a + s)
    return x + res * swiglu(p["mlp"], rms_norm(x, p["ln2"], eps), pr)
