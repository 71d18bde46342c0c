#!/usr/bin/env python3
"""Chip smoke: BlockLLM training and multi-tenant serving on one TPU.

    python chip_smoke.py

Drives both main paths once through the launchers a user calls, at the
full width of llama-350m (24 layers, d=1024, 16 heads, d_ff 2736,
vocab 32000; random weights from seed 0), then runs each main-path
Pallas kernel against its ``kernels/ref.py`` oracle:

  a. train   ``launch.train --optimizer blockllm``     (6 steps)
  b. train   ``launch.train --optimizer blockllm+q8``  (fused q8 kernel)
  c. serve   dense KV cache, XLA attention, 2 demo adapters
  d. serve   dense KV cache, Pallas decode attention
  e. serve   paged KV cache, fused Pallas paged decode attention
  f. kernels decode / paged decode attention, masked Adam (+q8) and
             scatter-swap vs their oracles

Every serve leg checks that each request got its tokens, that adapter
swaps ran (the Pallas scatter-swap), and that ``restore_base()`` gives
back the seeded base bit for bit.  Any failed check or exception ends
the run with a non-zero exit code.

It runs on a TPU only: with no TPU (or outside a checkout of the repo)
it says why and exits non-zero before any phase.  Everything it prints
is a smoke observation, not a benchmark number.  The last line of
stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import gc
import json
import math
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "llama-350m"


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _sync():
    import jax
    jax.block_until_ready(jax.live_arrays())


def _peak_mib() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2 ** 20:.1f} MiB"


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# --------------------------------------------------------------------- #
# a/b: training through launch.train
# --------------------------------------------------------------------- #


def phase_train(optimizer: str, *, arch=ARCH, reduce=0, steps=6, batch=8,
                seq=256):
    from repro.launch import train
    marks = []

    def on_step(step, metrics):
        _sync()
        marks.append(time.perf_counter())

    t0 = time.perf_counter()
    out = train.main(["--arch", arch, "--reduce", str(reduce),
                      "--optimizer", optimizer, "--steps", str(steps),
                      "--batch", str(batch), "--seq", str(seq)],
                     on_step=on_step)
    _sync()
    wall = time.perf_counter() - t0
    losses = out["losses"]
    check(len(losses) == steps, f"{optimizer}: {len(losses)} losses")
    check(all(math.isfinite(x) for x in losses),
          f"{optimizer}: non-finite loss in {losses}")
    steps_s = [b - a for a, b in zip(marks, marks[1:])]
    log(f"train {optimizer}: losses {[round(x, 4) for x in losses]}")
    log(f"train {optimizer}: wall {wall:.2f} s; set-up + compile + step 1 "
        f"{marks[0] - t0:.2f} s; later steps "
        f"{[round(s, 4) for s in steps_s]} s; steady (median of the last "
        f"3) {statistics.median(steps_s[-3:]):.4f} s/step "
        f"({batch * seq / statistics.median(steps_s[-3:]):.0f} tokens/s)")


# --------------------------------------------------------------------- #
# c/d/e: serving through launch.serve
# --------------------------------------------------------------------- #


def _bits_equal(a, b) -> bool:
    import jax
    import jax.numpy as jnp

    def same(x, y):
        if x.shape != y.shape or x.dtype != y.dtype:
            return False
        u = jnp.dtype(f"uint{8 * x.dtype.itemsize}")
        return bool(jnp.array_equal(jax.lax.bitcast_convert_type(x, u),
                                    jax.lax.bitcast_convert_type(y, u)))

    return all(jax.tree.leaves(jax.tree.map(same, a, b)))


def phase_serve(name: str, extra, *, arch=ARCH, reduce=0, slots=8,
                max_seq=1024, requests=8, new_tokens=16):
    import jax
    from repro.configs import base as config_base
    from repro.launch import serve
    from repro.launch.train import reduce_config
    from repro.models import model as model_lib

    t0 = time.perf_counter()
    reqs, srv = serve.main(
        ["--arch", arch, "--reduce", str(reduce), "--slots", str(slots),
         "--max-seq", str(max_seq), "--requests", str(requests),
         "--new-tokens", str(new_tokens), "--demo-adapters", "2",
         "--cache-bytes", str(4 * 2 ** 20)] + list(extra))
    _sync()
    wall = time.perf_counter() - t0
    check(all(len(r.out) == new_tokens for r in reqs),
          f"{name}: token counts {[len(r.out) for r in reqs]}")
    check(srv.swaps >= 1, f"{name}: no adapter swap ran")
    step = srv.metrics.histogram("decode/step_ms")
    log(f"serve {name}: wall {wall:.2f} s (set-up, adapters and compiles "
        f"included); {srv.steps} decode steps, "
        f"{srv.metrics.counter('sched/compiles').value} compiled; steady "
        f"decode step p50 {step.percentile(50):.3f} ms, p99 "
        f"{step.percentile(99):.3f} ms over {step.count} compile-free "
        f"steps; {srv.swaps} adapter swaps")

    srv.restore_base()
    cfg = config_base.get_config(arch)
    if reduce:
        cfg = reduce_config(cfg, reduce)
    base = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    check(_bits_equal(srv.params, base),
          f"{name}: params after restore_base() differ from the base")
    log(f"serve {name}: restore_base() gives the seeded base bit for bit")


# --------------------------------------------------------------------- #
# f: main-path kernels against their oracles
# --------------------------------------------------------------------- #


def _timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def _maxabs(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def phase_kernels(*, B=8, H=16, KV=16, hd=64, C=1024, ps=16, d=1024,
                  d_ff=2736, vocab=32000, layers=24, interpret=False):
    """Tolerances: attention 3e-2 max-abs (bf16 caches, f32 softmax; the
    MXU may round f32 operands to bf16); masked Adam 1e-5 + 1e-4 rel
    (elementwise f32); q8 moments within one int8 step; scatter-swap
    exact."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import decode_attention as da
    from repro.kernels import masked_adam as ma
    from repro.kernels import ref
    from repro.kernels import scatter_apply as sa

    key = iter(jax.random.split(jax.random.PRNGKey(0), 32))
    normal = lambda shape, dt=jnp.float32: jax.random.normal(
        next(key), shape, jnp.float32).astype(dt)
    hi = jax.default_matmul_precision("highest")
    rng = np.random.default_rng(0)

    # decode attention over a dense cache, ragged positions
    q = normal((B, 1, H, hd))
    kc, vc = normal((B, C, KV, hd), jnp.bfloat16), normal((B, C, KV, hd),
                                                          jnp.bfloat16)
    pos = jnp.asarray(rng.integers(0, C, B), jnp.int32)
    o, t1 = _timed(da.decode_attention_fwd, q, kc, vc, pos,
                   interpret=interpret)
    o, t2 = _timed(da.decode_attention_fwd, q, kc, vc, pos,
                   interpret=interpret)
    with hi:
        err = _maxabs(o, ref.decode_attention_ref(q, kc, vc, pos))
    check(err < 3e-2, f"decode_attention max-abs {err}")
    log(f"kernel decode_attention [B={B}, C={C}, H={H}, KV={KV}, hd={hd}]: "
        f"max-abs {err:.2e}; first call {t1:.3f} s, second {t2 * 1e3:.3f} ms")

    # paged decode attention: every slot owns NP pages, some slots idle
    NP = C // ps
    P = B * NP + 1
    tbl = jnp.asarray((rng.permutation(P - 1) + 1).reshape(B, NP), jnp.int32)
    act = jnp.asarray(np.arange(B) % 4 != 3)
    kp, vp = normal((P, ps, KV, hd), jnp.bfloat16), normal((P, ps, KV, hd),
                                                           jnp.bfloat16)
    nk, nv = normal((B, KV, hd)), normal((B, KV, hd))
    args = (q, nk, nv, kp, vp, pos, tbl, act)
    with hi:
        o_r, k_r, v_r = ref.paged_decode_attention_ref(*args)
    (o_k, k_k, v_k), t1 = _timed(da.paged_decode_attention_fwd, *args,
                                 interpret=interpret)
    _, t2 = _timed(da.paged_decode_attention_fwd, *args, interpret=interpret)
    live = np.asarray(act)
    err = _maxabs(o_k[live], o_r[live])
    check(err < 3e-2, f"paged_decode_attention max-abs {err}")
    # page 0 is the inactive slots' write sink: garbage by contract
    check(bool(jnp.array_equal(k_k[1:], k_r[1:]))
          and bool(jnp.array_equal(v_k[1:], v_r[1:])),
          "paged_decode_attention pool writes differ from the oracle")
    log(f"kernel paged_decode_attention [B={B}, pages {P}x{ps}, KV={KV}, "
        f"hd={hd}]: max-abs {err:.2e}, pool writes exact; first call "
        f"{t1:.3f} s, second {t2 * 1e3:.3f} ms")

    # masked Adam on the stacked MLP leaf's 2-D view [layers*d, d_ff]
    R = layers * d
    scal = jnp.asarray([1e-3, 0.9, 0.999, 1e-8, 0.0, 0.1, 0.001, 0.0],
                       jnp.float32)
    p, g = normal((R, d_ff)), normal((R, d_ff))
    m, v = normal((R, d_ff)) * 0.1, jnp.abs(normal((R, d_ff))) * 0.01
    mask = jax.random.bernoulli(next(key), 0.1, (R, d_ff))
    outs, t1 = _timed(ma.masked_adam_2d, p, g, m, v, mask, scal,
                      interpret=interpret)
    _, t2 = _timed(ma.masked_adam_2d, p, g, m, v, mask, scal,
                   interpret=interpret)
    for name, a, b in zip(("p", "m", "v"), outs,
                          ref.masked_adam_ref(p, g, m, v, mask, scal)):
        check(bool(jnp.allclose(a, b, rtol=1e-4, atol=1e-5)),
              f"masked_adam {name} max-abs {_maxabs(a, b)}")
    log(f"kernel masked_adam [{R}, {d_ff}] f32: within tolerance; first "
        f"call {t1:.3f} s, second {t2 * 1e3:.3f} ms")
    del outs, m, v

    # q8 masked Adam on the same leaf's [NB, 256] codec view
    NB = R * d_ff // 256
    pv, gv, mv = (p.reshape(NB, 256), g.reshape(NB, 256),
                  mask.reshape(NB, 256))
    mq = jax.random.randint(next(key), (NB, 256), -127, 128, jnp.int8)
    vq = jax.random.randint(next(key), (NB, 256), 0, 128, jnp.int8)
    ms = jnp.full((NB, 1), 1e-3, jnp.float32)
    vs = jnp.full((NB, 1), 1e-4, jnp.float32)
    q8 = (pv, gv, mq, ms, vq, vs, mv, scal)
    outs, t1 = _timed(ma.masked_adam_q8_2d, *q8, interpret=interpret)
    _, t2 = _timed(ma.masked_adam_q8_2d, *q8, interpret=interpret)
    want = ref.masked_adam_q8_ref(*q8)
    for name, a, b in zip(("p", "mq", "ms", "vq", "vs"), outs, want):
        if a.dtype == jnp.int8:
            d8 = int(jnp.max(jnp.abs(a.astype(jnp.int32)
                                     - b.astype(jnp.int32))))
            check(d8 <= 1, f"masked_adam_q8 {name} off by {d8}")
        else:
            check(bool(jnp.allclose(a, b, rtol=1e-4, atol=1e-5)),
                  f"masked_adam_q8 {name} max-abs {_maxabs(a, b)}")
    log(f"kernel masked_adam_q8 [{NB}, 256]: within tolerance; first call "
        f"{t1:.3f} s, second {t2 * 1e3:.3f} ms")
    del outs, want, q8, pv, gv, mv, p, g, mask

    # scatter-swap on the embedding and on a stacked leaf's 2-D view
    for G, width, K in ((vocab, d, 64), (layers, d * d_ff, 3)):
        full = normal((G, width))
        idx = jnp.asarray(rng.choice(G, K, replace=False), jnp.int32)
        rows = normal((K, width))
        want_full, want_disp = ref.scatter_swap_ref(full, idx, rows)
        orig = jnp.array(full, copy=True)
        (new, disp), t1 = _timed(sa.scatter_swap_2d, full, idx, rows,
                                 interpret=interpret)
        check(bool(jnp.array_equal(new, want_full))
              and bool(jnp.array_equal(disp, want_disp)),
              f"scatter_swap [{G}, {width}] differs from the oracle")
        (back, _), t2 = _timed(sa.scatter_swap_2d, new, idx, disp,
                               interpret=interpret)
        check(bool(jnp.array_equal(back, orig)),
              f"scatter_swap [{G}, {width}] is not an involution")
        log(f"kernel scatter_swap [{G}, {width}] K={K}: exact, involution "
            f"restores bit for bit; first call {t1:.3f} s, second "
            f"{t2 * 1e3:.3f} ms")
        del full, rows, want_full, want_disp, orig, new, disp, back


def run_phases(*, arch=ARCH, reduce=0, attn=("pallas", "pallas"),
               kernel_kw=None):
    """Phases a-f in order.  The defaults are the chip run; the keywords
    exist so the same code can be rehearsed at a tiny size."""
    phases = [
        ("a train blockllm", lambda: phase_train(
            "blockllm", arch=arch, reduce=reduce)),
        ("b train blockllm+q8", lambda: phase_train(
            "blockllm+q8", arch=arch, reduce=reduce)),
        ("c serve dense xla", lambda: phase_serve(
            "dense-xla", [], arch=arch, reduce=reduce)),
        ("d serve dense pallas", lambda: phase_serve(
            "dense-pallas", ["--attn-impl", attn[0]], arch=arch,
            reduce=reduce)),
        ("e serve paged pallas", lambda: phase_serve(
            "paged-pallas", ["--paged", "--attn-impl", attn[1]], arch=arch,
            reduce=reduce)),
        ("f kernels vs oracles", lambda: phase_kernels(
            **(kernel_kw or {}))),
    ]
    for name, fn in phases:
        t0 = time.perf_counter()
        log(f"phase {name}: start")
        fn()
        gc.collect()
        log(f"phase {name}: done in {time.perf_counter() - t0:.2f} s; "
            f"device peak_bytes_in_use so far {_peak_mib()}")


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro sources under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found {dev.platform} ({dev.device_kind})")
    entries = lambda: sum(1 for p in cache.rglob("*") if p.is_file())
    log("every number below is a smoke observation, not a benchmark")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; jax "
        f"{jax.__version__}; compile cache {cache} holds {entries()} "
        f"entries at start")
    t0 = time.perf_counter()
    run_phases()
    log(f"all phases passed in {time.perf_counter() - t0:.2f} s")
    log(f"compile cache {cache}: {entries()} entries at the end")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
