"""Plain reference of the first steps of a BlockLLM fine-tune, written
from the method's description and not from the program:

- selection: the static policy with an empty norm dictionary takes the
  first ``ceil(L * k_frac)`` layers of the stack, plus the final norm
  (always active) and any whole leaf the mix lets it select;
- ``q = n_s / sigma_p`` with ``n_s = round((1 - sparsity) * N)``;
- step 1 computes Adam's preconditioned gradient and keeps, per layer row
  of every leaf (per tensor for whole leaves), the elements whose magnitude
  reaches its ``1 - q`` quantile (estimated on a strided sample of at most
  ``quantile_sample`` elements per row); later steps keep that mask;
- every step is Adam with bias correction, the update multiplied by the
  mask, moments tracking every element of the selected rows.

Gradients are taken of the plain float32 model in ``configs/``.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

LAYER = [name for _, name in weights.LAYER_LEAVES]


def selected_rows(m: dict, mix: dict) -> List[int]:
    L = m["num_hidden_layers"]
    return list(range(max(1, math.ceil(L * mix["k_frac"]))))


def keep_fraction(m: dict, mix: dict) -> float:
    sh = weights.shapes(m)
    size = {k: int(np.prod(v)) for k, v in sh.items()}
    per_layer = sum(size[n] for n in LAYER)
    total = m["num_hidden_layers"] * per_layer + size["embed"] + size["head"] \
        + size["final_norm"]
    n_s = max(1, int(round((1.0 - mix["sparsity"]) * total)))
    sigma_p = len(selected_rows(m, mix)) * per_layer + size["final_norm"]
    sigma_p += sum(size[n] for n in mix["selectable_leaves"])
    return min(1.0, n_s / sigma_p)


def _threshold(u, keep, sample):
    flat = u.reshape(u.shape[0], -1)
    n = flat.shape[1]
    if n > sample:
        flat = flat[:, ::n // sample][:, :sample]
    return jnp.quantile(jnp.abs(flat), 1.0 - keep, axis=1)


def masks(upd: Dict[str, jax.Array], keep: float, sample: int):
    out = {}
    for n, u in upd.items():
        if n in LAYER:
            tau = _threshold(u, keep, sample)
            out[n] = jnp.abs(u) >= tau.reshape((-1,) + (1,) * (u.ndim - 1))
        else:
            out[n] = jnp.abs(u) >= _threshold(u.reshape(1, -1), keep, sample)[0]
    return out


def _loss(active, frozen, tokens, slot_of, m, ref, rnd):
    x = frozen["embed"][tokens]

    def body(x, inp):
        l, wl = inp
        s = slot_of[l]
        w = {n: jnp.where(s >= 0, active[n][jnp.maximum(s, 0)], wl[n])
             for n in LAYER}
        return ref.block(x, w, m, rnd), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x,
                        (jnp.arange(m["num_hidden_layers"]), frozen["layers"]))
    return ref.next_token_loss(x, active["final_norm"], frozen["head"],
                               tokens, m, rnd)


def steps(seed: int, m: dict, mix: dict, batches, ref, rnd=None,
          mask=None) -> dict:
    """Follow ``len(batches)`` steps from the seeded weights.  Returns the
    loss of each step, each leaf's gradient norm at step 1, each leaf's
    change over all the steps and the step-1 mask.  A
    given ``mask`` (leaf name -> bool array) replaces the step-1 mask: a
    calibration tool's look at what the mask alone changes, never a
    benchmark run's."""
    rnd = rnd or ref.ident
    p = weights.program_params(seed, m)
    blk = p["stages"][0]["pos0"]
    layers = {"ln1": blk["ln1"]["scale"], "ln2": blk["ln2"]["scale"],
              **blk["attn"], **blk["mlp"]}
    frozen = {"embed": p["embed"], "head": p["head"], "layers": layers}
    final_norm = p["final_norm"]["scale"]
    del p, blk
    rows = selected_rows(m, mix)
    slot_of = np.full(m["num_hidden_layers"], -1, np.int32)
    slot_of[rows] = np.arange(len(rows))
    idx = jnp.asarray(rows)
    active = {n: layers[n][idx] for n in LAYER}
    active["final_norm"] = final_norm
    if mix["selectable_leaves"]:
        raise ValueError("the reference selects no whole leaf but the "
                         f"final norm: {mix['selectable_leaves']}")
    keep = keep_fraction(m, mix)
    b1, b2, eps, lr = mix["b1"], mix["b2"], mix["eps"], mix["lr"]

    grad = jax.jit(jax.value_and_grad(
        lambda a, f, t: _loss(a, f, t, jnp.asarray(slot_of), m, ref, rnd)))
    mu = jax.tree.map(jnp.zeros_like, active)
    nu = jax.tree.map(jnp.zeros_like, active)
    losses, gnorms = [], {}
    for t, toks in enumerate(batches, start=1):
        loss, g = grad(active, frozen, jnp.asarray(toks))
        losses.append(float(loss))
        if t == 1:
            gnorms = {n: float(jnp.linalg.norm(v)) for n, v in g.items()}
        mu = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, mu, g)
        nu = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, nu, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        upd = jax.tree.map(lambda a, b: (a / bc1) / (jnp.sqrt(b / bc2) + eps),
                           mu, nu)
        if t == 1:
            own = masks(upd, keep, mix["quantile_sample"])
            mask = own if mask is None else {
                n: jnp.asarray(v).reshape(own[n].shape) for n, v in mask.items()}
        active = jax.tree.map(lambda p_, u, k: p_ - lr * u * k, active, upd,
                              mask)
    change = weights.change_norms(seed, m, rows, active)
    return {"losses": losses, "grad_norms": gnorms, "change_norms": change,
            "mask": own}


def gaps(program: dict, reference: dict) -> Dict[str, float]:
    """The three compared numbers: the gap between the first step's loss
    and the reference's (the later steps' losses follow which elements at
    the step-1 mask's threshold each side keeps, about 3% of them differing
    between bfloat16 and float32 gradients, and do not separate from the
    control's: see PERF.md), and by the
    worst leaf the gap between the program's and the reference's gradient
    norm (step 1) and change norm (all steps), each against the larger of
    that leaf's reference norm and the median leaf's.  Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out."""
    rg, rc = reference["grad_norms"], reference["change_norms"]
    med_g = float(np.median(list(rg.values())))
    kept = [n for n in rg if rg[n] >= 1e-3 * med_g]
    med_c = float(np.median([rc[n] for n in kept]))

    def worst(pv, rv, med):
        return max(abs(pv[n] - rv[n]) / max(rv[n], med) for n in kept)

    return {
        "loss_gap": abs(program["losses"][0] - reference["losses"][0]),
        "grad_gap": worst(program["grad_norms"], rg, med_g),
        "change_gap": worst(program["change_norms"], rc, med_c),
    }
