"""Decide ``correct``: what the timed path produced against the reference.

The window's own entry, ``VolunteerTrainer.round`` as ``train.main`` wires
it, drives the program through its first steps during set-up, at the
cell's sizes and on rows that all differ.  The reference follows the same
steps from the same seed.  Three numbers are compared, each by its gap to
the reference:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap``: the first gradient as the optimizer got it, read back from
  its first moment (``m = (1 - beta1) g``), by the worst leaf;
* ``change_gap``: the parameters' change over the steps, by the worst leaf,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (Adam moves those by round-off alone).

A leaf's gap is ``|norm_program - norm_reference|`` over the larger of the
reference leaf's norm and the median leaf's.  Where the cell snapshots,
``snapshot_gap`` counts the leaves of the newest restorable snapshot whose
bytes do not sum to what the program's state held after that round.
"""
from __future__ import annotations

import functools
import statistics

import numpy as np

NEGLIGIBLE_GRAD = 1e-3      # of the median leaf's gradient norm


FAULTS = ("half_batch", "altered")


def follow(cell, seed: int, steps: int, precision: str = "float32",
           fault: str | None = None) -> dict:
    """Run the plain reference through the cell's first ``steps`` steps.

    ``fault`` plants one of a training cell's faults in it, for reading
    what the fault does to the compared numbers: ``half_batch`` takes each
    unit's loss over half of its rows, ``altered`` makes each unit's loss
    (and so its gradient) 1% off.

    -> {"loss": [per step], "grad": {leaf: norm}, "change": {leaf: norm},
        "shapes": {leaf: shape}}."""
    import jax
    import jax.numpy as jnp

    from perfbench import reference as R
    c, t = cell.config, cell.traffic
    o, units = t["optimizer"], t["units_per_round"]
    ref = cell.reference()
    pr = R.Precision(precision)
    specs = ref.param_specs(c)

    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")

    def loss(p, tokens, labels):
        if fault == "half_batch":
            tokens, labels = tokens[: len(tokens) // 2], labels[: len(labels) // 2]
        val = R.lm_loss(p, tokens, labels, c, pr, ref.block)
        return val * 1.01 if fault == "altered" else val

    grad = jax.jit(jax.value_and_grad(loss))
    update = jax.jit(functools.partial(R.adamw, o))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    norms = jax.jit(lambda tree: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(x * x)), tree))
    delta_norms = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum((x - y) ** 2)), a, b))
    with jax.default_matmul_precision("highest"):
        p0 = R.init_params(specs, seed, c["init_std"])
        params = p0
        m = jax.tree.map(jnp.zeros_like, p0)
        v = jax.tree.map(jnp.zeros_like, p0)
        losses, first = [], None
        for s in range(steps):
            step_loss, g = [], None
            for k in range(units):
                tok, lab = R.token_rows(seed, s * units + k, t["data"],
                                        c["vocab_size"], t["batch"],
                                        t["seq_len"])
                val, gk = grad(params, tok, lab)
                step_loss.append(float(val))
                g = gk if g is None else add(g, gk)
            g = jax.tree.map(lambda x: x / units, g)
            params, m, v, clipped = update(params, g, m, v,
                                           *R.adamw_factors(o, s + 1))
            del g
            if s == 0:
                first = _by_path(jax.device_get(norms(clipped)))
            del clipped
            losses.append(float(np.mean(step_loss)))
        change = _by_path(jax.device_get(delta_norms(params, p0)))
        shapes = _by_path(jax.tree.map(lambda x: tuple(x.shape), p0,
                                       is_leaf=lambda x: hasattr(x, "shape")))
    return {"loss": losses, "grad": first, "change": change,
            "shapes": shapes}


def _by_path(tree) -> dict:
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))[0]
    return {jax.tree_util.keystr(p): (tuple(v) if isinstance(v, tuple)
                                      else float(v)) for p, v in leaves}


def _worst_leaf(prog: dict, ref: dict, keys: list) -> float:
    med = statistics.median(ref[k] for k in keys)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def gaps(prog: dict, ref: dict) -> dict:
    """The compared numbers from the program's and the reference's
    readings (same keys as ``follow`` returns)."""
    if prog["shapes"] != ref["shapes"]:
        return {"layout_gap": float(sum(
            prog["shapes"].get(k) != ref["shapes"].get(k)
            for k in set(prog["shapes"]) | set(ref["shapes"])))}
    keys = sorted(ref["grad"])
    med = statistics.median(ref["grad"][k] for k in keys)
    moved = [k for k in keys if ref["grad"][k] >= NEGLIGIBLE_GRAD * med]
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["loss"], ref["loss"])),
        "grad_gap": _worst_leaf(prog["grad"], ref["grad"], keys),
        "change_gap": _worst_leaf(prog["change"], ref["change"], moved),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit"}}).  A number with no limit
    of its own (a layout mismatch) must read 0."""
    out = {}
    for name, value in numbers.items():
        limit = limits[name]["limit"] if name in limits else 0.0
        out[name] = {"value": value, "limit": limit}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in out.values())
    return bool(ok), out


# ---------------------------------------------------------------------------
# Snapshot contents
# ---------------------------------------------------------------------------
def state_sums_fn():
    """Jitted: per leaf, (sum, index-weighted sum) of its 32-bit words,
    both modulo 2 ** 32."""
    import jax
    import jax.numpy as jnp

    def one(x):
        # the row-major index from per-axis iotas, not a flattening
        # reshape: the sums then fuse into one pass with no copy of the leaf
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        idx = jnp.zeros(u.shape, jnp.uint32)
        stride = 1
        for axis in reversed(range(u.ndim)):
            idx = idx + jax.lax.broadcasted_iota(
                jnp.uint32, u.shape, axis) * jnp.uint32(stride)
            stride *= u.shape[axis]
        w = idx * jnp.uint32(2) + jnp.uint32(1)
        return jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                          jnp.sum(u * w, dtype=jnp.uint32)])

    return jax.jit(lambda tree: jax.tree.map(one, tree))


def host_sums(data: bytes) -> tuple[int, int]:
    """The same two sums over a restored leaf's bytes."""
    u = np.frombuffer(data, dtype=np.uint32)
    w = np.arange(u.size, dtype=np.uint32) * np.uint32(2) + np.uint32(1)
    return int(np.sum(u, dtype=np.uint32)), int(np.sum(u * w,
                                                       dtype=np.uint32))
