"""Plain reference of the first steps of a BlockLLM fine-tune, written
from the method's description and not from the program:

- selection: the static policy with an empty norm dictionary takes the
  first ``ceil(rows * k_frac)`` rows of every stack, plus the final norm
  (always active) and any whole leaf the mix lets it select;
- ``q = n_s / sigma_p`` with ``n_s = round((1 - sparsity) * N)``, ``N``
  every parameter and ``sigma_p`` the selected ones;
- step 1 computes Adam's preconditioned gradient and keeps, per row of
  every stacked leaf (per tensor for top leaves), the elements whose
  magnitude reaches its ``1 - q`` quantile (estimated on a strided sample
  of at most ``quantile_sample`` elements per row); later steps keep that
  mask;
- every step is Adam with bias correction, the update multiplied by the
  mask, moments tracking every element of the selected rows.

Gradients are taken of the plain float32 model in ``configs/``, layer by
layer in the program's order; which leaves and stacks there are comes
from the configuration's layout (``bench/layouts``).  Every number is
keyed by the leaf's program path.
"""
from __future__ import annotations

import math
from itertools import groupby
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import layouts, weights

# top leaves the static policy always selects (the program's
# ``SelectorConfig.always_active_leaves``)
ALWAYS_ACTIVE = ("final_norm",)


def _top(leaf: layouts.Leaf) -> str:
    return leaf.path.split("/")[0]


def selected_rows(m: dict, mix: dict) -> Dict[str, List[int]]:
    return {s.sid: list(range(max(1, math.ceil(s.rows * mix["k_frac"]))))
            for s in layouts.of(m).stacks(m)}


def keep_fraction(m: dict, mix: dict) -> float:
    rows = selected_rows(m, mix)
    tab = layouts.table(m).values()
    total = sum(layouts.numel(m, l) for l in tab)
    n_s = max(1, int(round((1.0 - mix["sparsity"]) * total)))
    sigma_p = sum(l.size * len(rows[l.stack]) for l in tab if l.stack)
    sigma_p += sum(l.size for l in tab if not l.stack and (
        _top(l) in ALWAYS_ACTIVE or _top(l) in mix["selectable_leaves"]))
    return min(1.0, n_s / sigma_p)


def _threshold(u, keep, sample):
    flat = u.reshape(u.shape[0], -1)
    n = flat.shape[1]
    if n > sample:
        flat = flat[:, ::n // sample][:, :sample]
    return jnp.quantile(jnp.abs(flat), 1.0 - keep, axis=1)


def masks(upd: Dict[str, jax.Array], keep: float, sample: int, tab):
    out = {}
    for p, u in upd.items():
        if tab[p].stack:
            tau = _threshold(u, keep, sample)
            out[p] = jnp.abs(u) >= tau.reshape((-1,) + (1,) * (u.ndim - 1))
        else:
            out[p] = jnp.abs(u) >= _threshold(u.reshape(1, -1), keep, sample)[0]
    return out


def _loss(active, frozen, tokens, slot_of, m, ref, rnd, stages, top):
    """Next-token loss plus every layer's auxiliary loss.  ``stages``: for
    each of the program's stages, its stacks in order with their leaves;
    a stage runs its rows in turn, each row through its stacks in order."""
    x = frozen[top["embed"]][tokens]
    aux = jnp.zeros((), jnp.float32)
    for stage in stages:
        paths = [l.path for _, lvs in stage for l in lvs]

        def body(carry, inp, stage=stage):
            x, aux = carry
            g, wl = inp
            for st, lvs in stage:
                s = slot_of[st.sid][g]
                w = {l.name: jnp.where(s >= 0, active[l.path][jnp.maximum(s, 0)],
                                       wl[l.path]) for l in lvs}
                x, a = getattr(ref, st.block)(x, w, m, rnd)
                aux = aux + a
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(
            jax.checkpoint(body), (x, aux),
            (jnp.arange(stage[0][0].rows), {p: frozen[p] for p in paths}))
    return ref.next_token_loss(x, active[top["final_norm"]],
                               frozen[top["head"]], tokens, m, rnd) + aux


def steps(seed: int, m: dict, mix: dict, batches, ref, rnd=None,
          mask=None) -> dict:
    """Follow ``len(batches)`` steps from the seeded weights.  Returns the
    loss of each step, each leaf's gradient norm at step 1, each leaf's
    change over all the steps and the step-1 mask, by program path.  A
    given ``mask`` (path -> bool array) replaces the step-1 mask: a
    calibration tool's look at what the mask alone changes, never a
    benchmark run's."""
    rnd = rnd or ref.ident
    if mix["selectable_leaves"]:
        raise ValueError("the reference selects no whole leaf but the "
                         f"final norm: {mix['selectable_leaves']}")
    lay = layouts.of(m)
    tab = layouts.table(m)
    stacks = lay.stacks(m)
    by_stack = {s.sid: [l for l in tab.values() if l.stack == s.sid]
                for s in stacks}
    stages = [[(s, by_stack[s.sid]) for s in group]
              for _, group in groupby(stacks, key=lambda s: s.si)]
    top = {l.name: l.path for l in tab.values() if not l.stack}
    frozen = weights.by_path(weights.program_params(seed, m))
    rows = selected_rows(m, mix)
    slot_of = {}
    for s in stacks:
        slot = np.full(s.rows, -1, np.int32)
        slot[rows[s.sid]] = np.arange(len(rows[s.sid]))
        slot_of[s.sid] = jnp.asarray(slot)
    active = {l.path: frozen[l.path][jnp.asarray(rows[l.stack])]
              for l in tab.values() if l.stack}
    active.update({l.path: frozen.pop(l.path) for l in tab.values()
                   if not l.stack and _top(l) in ALWAYS_ACTIVE})
    keep = keep_fraction(m, mix)
    b1, b2, eps, lr = mix["b1"], mix["b2"], mix["eps"], mix["lr"]

    grad = jax.jit(jax.value_and_grad(
        lambda a, f, t: _loss(a, f, t, slot_of, m, ref, rnd, stages, top)))
    mu = jax.tree.map(jnp.zeros_like, active)
    nu = jax.tree.map(jnp.zeros_like, active)
    losses, gnorms = [], {}
    for t, toks in enumerate(batches, start=1):
        loss, g = grad(active, frozen, jnp.asarray(toks))
        losses.append(float(loss))
        if t == 1:
            gnorms = {p: float(jnp.linalg.norm(v)) for p, v in g.items()}
        mu = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, mu, g)
        nu = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, nu, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        upd = jax.tree.map(lambda a, b: (a / bc1) / (jnp.sqrt(b / bc2) + eps),
                           mu, nu)
        if t == 1:
            own = masks(upd, keep, mix["quantile_sample"], tab)
            mask = own if mask is None else {
                p: jnp.asarray(v).reshape(own[p].shape) for p, v in mask.items()}
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
