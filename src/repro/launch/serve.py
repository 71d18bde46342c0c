"""Serving launcher: batched greedy decode over a request file or demo set.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduce 8

Multi-tenant: point ``--adapters`` at a BlockDelta registry directory
(see repro.adapters) and requests are spread across the base model and
every stored adapter — one resident base, deltas hot-swapped between
decode micro-batches.  The scheduler is adapter-aware by default: free
slots are filled with the resident adapter's queued requests before
rotating, turn lengths scale per adapter with queue depth and
``--slo-ms`` deadlines, and an aging bound prevents starvation
(``--round-robin`` restores the PR-1 rotation for A/B comparison):

    PYTHONPATH=src python -m repro.launch.serve --adapters /path/to/reg

``--cache-bytes`` keeps hot adapters' delta rows resident in HBM
(``repro.adapters.AdapterCache``): tenant flips whose delta is cached
are device-to-device scatter-swaps with zero host->device transfer.

FastDecode hot path: prompts are primed by **chunked batched prefill**
(``--prefill-chunk``, 0 restores per-token priming) — one full-sequence
dispatch per prompt chunk per admitted group instead of one decode
dispatch per prompt token per request — and ``--attn-impl pallas``
selects the fused Pallas decode-attention kernel whose HBM reads scale
with each slot's actual context length instead of ``--max-seq``
(``--attn-impl full`` is the grouped-einsum XLA fallback).
``--ms-per-step auto`` calibrates SLO slack from a wall-clock EMA of
the measured decode-step time.

PagedKV (``--paged``): the KV cache becomes a pool of fixed-size pages
(``--kv-page-size`` rows each, ``--kv-pages`` total; 0 = the dense
equivalent) addressed through per-slot page tables — HBM is paid per
live token, admission turns continuous (requests retire and admit
every decode step against page capacity), and tenants sharing a prompt
prefix share physical pages copy-on-write.  The demo request set gives
every tenant a common system-prompt prefix so prefix hits and COW
splits show up in the ``kv`` stats section; token streams are
bit-identical to ``--dense`` (the default).

SpecServe (``--speculate N``): self-speculative decoding — the
always-resident base model drafts N tokens per scheduler step through
the plain decode path, then the tenant's adapter-applied model scores
all N+1 positions in one chunked verify dispatch and the longest
greedy-agreeing prefix is accepted.  No second draft model: under
BlockDelta a tenant differs from the base by <5% of rows, so the
base↔adapter flip is a device scatter-swap.  Streams are bit-identical
to non-speculative greedy serving; the draft length adapts per tenant
as acceptance moves.  ``spec/*`` counters land in stats/traces.

Serving-side regressions are gated in CI by ``tools/check_serving.py``
against ``benchmarks/serve_baselines.json`` (re-baseline deliberately
with ``--update``); the decode hot path itself is covered by
``benchmarks/bench_decode_path.py``.
"""
from __future__ import annotations

import argparse
from pathlib import Path


def add_serve_config_flags(ap: argparse.ArgumentParser) -> None:
    """Flags that map onto ``ServeConfig`` (shared with launch.fleet).

    ``--config path.json`` loads a serialized ServeConfig instead of
    building one from the flags below; ``--save-config path.json``
    writes the effective config back out — the pair round-trips
    bit-exactly (``ServeConfig.from_json(cfg.to_json()) == cfg``).
    """
    ap.add_argument("--config", default=None, metavar="PATH",
                    help="load a ServeConfig JSON (overrides the "
                         "serve-shape flags below)")
    ap.add_argument("--save-config", default=None, metavar="PATH",
                    help="write the effective ServeConfig JSON "
                         "(reload it with --config)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--steps-per-turn", type=int, default=8,
                    help="base decode steps per adapter group before "
                         "rotating (per-adapter budgets scale from "
                         "this)")
    ap.add_argument("--cache-bytes", type=int, default=0,
                    help="HBM byte budget for the AdapterCache "
                         "(delta rows kept device-resident; 0 = "
                         "uncached, every flip re-uploads host rows)")
    ap.add_argument("--aging-steps", type=int, default=0,
                    help="anti-starvation bound in decode steps "
                         "(0 = 3x steps-per-turn)")
    ap.add_argument("--round-robin", action="store_true",
                    help="disable adapter-aware admission (PR-1 "
                         "rotation baseline)")
    ap.add_argument("--attn-impl", default="full",
                    choices=["full", "pallas", "pallas_interpret"],
                    help="decode attention: 'pallas' = fused kernel "
                         "(HBM reads scale with per-slot context), "
                         "'full' = grouped-einsum XLA fallback, "
                         "'pallas_interpret' = kernel in interpret "
                         "mode (CPU debugging)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="prompt positions per chunked-prefill "
                         "dispatch (0 = legacy per-token priming)")
    kv = ap.add_mutually_exclusive_group()
    kv.add_argument("--paged", action="store_true",
                    help="PagedKV: block-paged KV cache + continuous "
                         "batching + copy-on-write prefix sharing")
    kv.add_argument("--dense", action="store_true",
                    help="dense [slots, max_seq] KV cache (default)")
    ap.add_argument("--kv-page-size", type=int, default=16,
                    help="token rows per KV page (must divide "
                         "--max-seq)")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="physical pages in the pool (0 = dense "
                         "equivalent: slots * max_seq / page_size + "
                         "1; smaller oversubscribes slots against "
                         "aggregate live tokens)")
    ap.add_argument("--no-prefix-share", action="store_true",
                    help="disable copy-on-write prompt prefix sharing "
                         "between paged requests")
    sp = ap.add_mutually_exclusive_group()
    sp.add_argument("--speculate", type=int, default=0, metavar="N",
                    help="SpecServe: the always-resident base model "
                         "drafts N tokens per scheduler step and the "
                         "adapter model verifies all N+1 positions in "
                         "one dispatch; streams stay bit-identical to "
                         "greedy serving (0 = off)")
    sp.add_argument("--no-speculate", action="store_true",
                    help="force speculative decoding off (explicit A/B "
                         "baseline against --speculate)")
    ap.add_argument("--ms-per-step", default="1.0",
                    help="SLO conversion: decode-step time in ms, or "
                         "'auto' to calibrate from a wall-clock EMA")


def serve_config_from_args(args):
    """Build the effective ``ServeConfig`` from parsed flags (or load
    ``--config``), honoring ``--save-config``."""
    from repro.runtime.serve_config import (KVConfig, SchedConfig,
                                            ServeConfig, SpecConfig)
    if args.config:
        cfg = ServeConfig.from_json(Path(args.config).read_text())
    else:
        cfg = ServeConfig(
            batch_slots=args.slots,
            max_seq=args.max_seq,
            attn_impl=args.attn_impl,
            prefill_chunk=args.prefill_chunk,
            sched=SchedConfig(
                steps_per_turn=args.steps_per_turn,
                adapter_aware=not args.round_robin,
                aging_steps=args.aging_steps,
                ms_per_step=("auto" if args.ms_per_step == "auto"
                             else float(args.ms_per_step)),
                cache_bytes=args.cache_bytes),
            kv=KVConfig(
                layout="paged" if args.paged else "dense",
                page_size=args.kv_page_size,
                pages=args.kv_pages,
                prefix_share=not args.no_prefix_share),
            spec=SpecConfig(
                draft=0 if args.no_speculate else args.speculate))
    if args.save_config:
        p = Path(args.save_config)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(cfg.to_json())
        print(f"serve config -> {p}")
    return cfg


def make_demo_registry(params, n: int):
    """N synthetic tenants: row-perturbed copies of the base published
    to an in-memory registry — exercises the full swap/scheduling path
    without a registry dir (the CI smokes assert swap spans appear)."""
    from repro.adapters import extract_delta
    from repro.adapters.registry import InMemoryRegistry
    from repro.adapters.testing import perturb_rows
    registry = InMemoryRegistry()
    ids = []
    for i in range(n):
        aid = f"demo{i}"
        tuned = perturb_rows(params, rows=(1 + i % 2, 3), seed=i)
        registry.put(aid, extract_delta(params, tuned,
                                        meta={"adapter_id": aid}))
        ids.append(aid)
    return registry, ids


def main(argv=None):
    """Serve the request set; returns ``(requests, drained server)``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-60m")
    ap.add_argument("--reduce", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--adapters", default=None,
                    help="BlockDelta registry dir: serve every stored "
                         "adapter alongside the base model")
    ap.add_argument("--tenants", default="all",
                    help="comma-separated adapter ids to serve "
                         "(default: all in the registry)")
    ap.add_argument("--slo-ms", type=float, default=0,
                    help="per-request deadline budget (0 = none); "
                         "groups whose slack runs low preempt the "
                         "rotation order")
    add_serve_config_flags(ap)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a TraceKit trace of the run: .jsonl = "
                         "event log, anything else = Chrome/Perfetto "
                         "trace JSON (load at ui.perfetto.dev)")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="dump the metrics registry as text every N "
                         "decode steps (0 = only the final summary)")
    ap.add_argument("--quick", action="store_true",
                    help="small smoke preset: fewer requests/tokens "
                         "(CI trace-smoke uses this)")
    ap.add_argument("--demo-adapters", type=int, default=0,
                    help="build N synthetic in-memory adapters (row "
                         "perturbations of the base) so multi-tenant "
                         "scheduling/swaps run without a registry dir")
    args = ap.parse_args(argv)
    if args.quick:
        args.requests = min(args.requests, 6)
        args.new_tokens = min(args.new_tokens, 8)
        args.reduce = max(args.reduce, 8)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import numpy as np
    from repro.configs import base as config_base
    from repro.launch.train import reduce_config
    from repro.models import model as model_lib
    from repro.runtime.serve_loop import DecodeServer, Request

    cfg = config_base.get_config(args.arch)
    if args.reduce:
        cfg = reduce_config(cfg, args.reduce)
    if cfg.is_encoder_decoder or cfg.family == "vlm":
        raise SystemExit("serve demo supports LM-family archs")
    params = model_lib.init_params(jax.random.PRNGKey(args.seed), cfg)

    registry, tenants = None, [None]
    if args.adapters:
        from repro.adapters import AdapterRegistry
        registry = AdapterRegistry(args.adapters)
        ids = (registry.list_adapters() if args.tenants == "all"
               else [t for t in args.tenants.split(",") if t])
        missing = [t for t in ids if not registry.exists(t)]
        if missing:
            raise SystemExit(f"adapters not in registry: {missing}")
        tenants += ids
        print(f"multi-tenant: base + {len(ids)} adapter(s) {ids}")
    elif args.demo_adapters > 0:
        registry, ids = make_demo_registry(params, args.demo_adapters)
        tenants += ids
        print(f"multi-tenant: base + {len(ids)} demo adapter(s) {ids}")

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()

    serve_cfg = serve_config_from_args(args)
    srv = DecodeServer(cfg, params, serve_cfg, registry=registry,
                       tracer=tracer)
    rng = np.random.default_rng(args.seed)
    # paged demo requests share a system-prompt prefix (sized past one
    # KV page so full prefix pages AND a partial tail register —
    # admissions after the first then log prefix hits, and the tail's
    # first decode write logs a COW split); dense runs keep the short
    # prompts so small --max-seq demos don't truncate
    sys_prompt = (rng.integers(0, cfg.vocab_size,
                               args.kv_page_size + args.kv_page_size // 2)
                  if args.paged else
                  np.zeros(0, np.int64))
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [sys_prompt,
                         rng.integers(0, cfg.vocab_size, 4 + i % 4)]),
                    max_new_tokens=args.new_tokens,
                    adapter_id=tenants[i % len(tenants)],
                    slo_ms=args.slo_ms or None)
            for i in range(args.requests)]
    for r in reqs:
        srv.submit(r)
    import time

    def _periodic(s):
        if args.metrics_every and s.steps \
                and s.steps % args.metrics_every == 0:
            print(f"-- metrics @ decode step {s.steps} --")
            print(s.metrics.dump_text(), flush=True)

    on_step = _periodic if args.metrics_every else None
    t0 = time.monotonic()
    try:
        srv.run_until_drained(on_step=on_step)
    except KeyboardInterrupt:
        # graceful drain: finish the in-flight work, then fall through
        # to the normal stats/trace flush so nothing observed is lost
        pending = sum(1 for r in reqs if not r.done)
        print(f"\ninterrupted at decode step {srv.steps}: draining "
              f"{pending} in-flight request(s) before exit "
              f"(^C again to abort the drain)")
        try:
            srv.run_until_drained(on_step=on_step)
        except KeyboardInterrupt:
            print("drain aborted; stats and trace below reflect the "
                  "partial run")
    dt = time.monotonic() - t0
    tok = sum(len(r.out) for r in reqs)
    print(f"served {len(reqs)} requests, {tok} tokens in {dt:.2f}s "
          f"({tok / dt:.1f} tok/s, {srv.steps} decode steps)")
    print(f"prefill: {srv.prefill_prompt_tokens} prompt tokens in "
          f"{srv.prefill_dispatches} dispatches "
          f"({'chunked' if srv._slot_prefill else 'per-token'}, "
          f"chunk {srv.prefill_chunk})"
          + (f"; ms/step EMA {srv.ms_per_step:.2f}"
             if args.ms_per_step == "auto" else ""))
    if srv.speculate:
        sps = srv.stats()["spec"]
        print(f"speculative: {sps['rounds']} rounds, "
              f"{sps['drafted']} drafted / {sps['accepted']} accepted "
              f"({sps['acceptance_rate']:.0%}), "
              f"{sps['rollbacks']} rollbacks, {sps['flips']} flips, "
              f"{sps['tokens_per_step']:.2f} tokens/round")
    if srv.alloc is not None:
        kvs = srv.stats()["kv"]
        al = srv.alloc
        print(f"paged KV: {al.num_pages} pages x {al.page_size} rows, "
              f"{kvs['page_alloc']} allocs / {kvs['page_free']} frees, "
              f"{kvs['cow_split']} COW splits, "
              f"prefix hits {kvs['prefix_hit_pages']} pages "
              f"({kvs['prefix_hit_tokens']} tokens), "
              f"{kvs['pages_in_use']} in use at drain")
    if registry is not None:
        sched = srv.stats()["sched"]
        reg_stats = getattr(registry, "stats", dict)()
        print(f"adapter swaps: {sched['swaps']} "
              f"({sched['swap_rate']:.3f}/step), "
              f"{sched['swap_bytes'] / 2 ** 20:.2f} MiB moved; "
              f"registry: {reg_stats}")
        if srv.cache is not None:
            c = srv.cache.stats()
            print(f"adapter cache: {c['resident']} resident "
                  f"({c['resident_bytes'] / 2 ** 20:.2f} / "
                  f"{c['cache_bytes'] / 2 ** 20:.2f} MiB), "
                  f"hit rate {c['hit_rate']:.0%}, "
                  f"h2d {c['h2d_bytes'] / 2 ** 20:.2f} MiB vs "
                  f"d2d {c['d2d_bytes'] / 2 ** 20:.2f} MiB")
    if tracer is not None:
        from repro.obs import write_trace
        p = write_trace(args.trace, tracer, srv.metrics)
        print(f"trace: {len(tracer)} events -> {p}")
    for r in reqs[:3]:
        tag = f" [{r.adapter_id or 'base'}]"
        print(f"  req {r.rid}{tag}: {list(r.prompt)} -> {r.out}")
    return reqs, srv


if __name__ == "__main__":
    main()
