"""Quickstart: BlockLLM through the functional TrainerCore API.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro import trainers
from repro.configs.base import ModelConfig
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.models import model
from repro.optim.adam import Adam

# 1. describe a model (any of the 10 assigned archs works via
#    repro.configs.base.get_config("gemma3-1b") etc.)
cfg = ModelConfig(name="demo", family="dense", num_layers=6, d_model=128,
                  num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512,
                  remat=False)
params = model.init_params(jax.random.PRNGKey(0), cfg)

# 2. resolve the trainer by name from the registry ("blockllm", "adam",
#    "galore", "lora", "badam" all speak the same init/step protocol).
#    BlockLLM: only ~10% of parameters get gradients + Adam state; blocks
#    re-selected by gradient norm / visit frequency when the loss
#    plateaus (paper Algorithm 1+2).
#    Add quantize_state=True (or use the "blockllm+q8" registry name /
#    `launch.train --quantize-state`) for Q8State: Adam moments stored
#    int8 + per-block scales at ~25% of the fp32 bytes, same protocol,
#    bit-exact crash-resume.
core = trainers.make("blockllm", cfg, adam=Adam(lr=1e-3),
                     sparsity=0.9, patience=20, policy="static",
                     k_frac=0.25)
state = core.init(jax.random.PRNGKey(0), params)

# 3. train on the deterministic synthetic pipeline — state in, state out
pipe = TokenPipeline(DataConfig(vocab_size=512, seq_len=64, global_batch=8))
for step in range(30):
    state, metrics = core.step(state, pipe.batch(step))
    if step % 10 == 0:
        print(f"step {step}: loss={metrics['loss']:.4f}")

rep = core.memory_report(state)
print(f"\nfinal loss  : {metrics['loss']:.4f}")
print(f"train state : {rep['total_train_state'] / 2**20:.2f} MiB "
      f"(grads+opt+masks, vs params {rep['params_bytes'] / 2**20:.2f} MiB)")
print(f"re-selections: {state.meta['reselections']}, "
      f"recompiles: {core.recompiles} (static policy: stays at 2)")

# 4. serving: a finetune exports as a row-sparse SparseDelta
#    (TrainLoopConfig.adapter_dir) and `launch.serve --adapters <dir>`
#    multiplexes many such tenants over ONE resident base model.
#    `--cache-bytes` keeps hot deltas HBM-resident (device-to-device
#    flips), `--slo-ms` sets per-request deadlines for the
#    adapter-aware scheduler (`--ms-per-step auto` calibrates the
#    deadline clock from measured step time); see
#    examples/multi_tenant_serve.py for the end-to-end proof.
#    The decode hot path is FastDecode: prompts prime via chunked
#    batched prefill (`--prefill-chunk`, ceil(P/chunk) dispatches per
#    admitted group instead of P per request) and `--attn-impl pallas`
#    selects the fused decode-attention kernel whose HBM reads scale
#    with each slot's actual context instead of --max-seq
#    (benchmarks/bench_decode_path.py measures both).  Serving perf is
#    CI-gated: re-baseline deliberately with
#    `python tools/check_serving.py --update`.

# 5. Paged serving (PagedKV, runtime/paged_kv.py).  `--paged` swaps the
#    dense [slots, max_seq] KV cache for fixed-size pages on a
#    free-list with per-slot page tables, so HBM is paid per live token
#    (rounded to a page) instead of per worst-case request:
#
#        PYTHONPATH=src python -m repro.launch.serve \
#            --quick --paged --kv-page-size 16 --kv-pages 0
#
#    `--kv-pages 0` sizes the pool dense-equivalent; pass fewer pages to
#    oversubscribe slots against aggregate tokens — admission is
#    continuous (requests admit/retire every decode step against page
#    capacity, worst-case reserved so the loop never wedges) and a
#    mixed-length workload admits >=2x the concurrent requests at equal
#    KV HBM.  Tenants sharing a system prompt share physical pages:
#    prefilled prompt pages register in a prefix registry, later
#    requests map them copy-on-write and skip re-prefilling the shared
#    tokens (`--no-prefix-share` disables).  Decoded token streams are
#    bit-identical to dense serving in every scheduler configuration;
#    `Request.on_token` streams tokens as they decode
#    (examples/chat_serve.py measures TTFT/TPS per chat turn on a
#    shared system prompt).  `DecodeServer.stats()["kv"]` reports
#    page_alloc/page_free/cow_split/prefix_hit/pages_in_use, and the
#    same counters land in traces as kv-lane instants.

# 6. Tracing a serve session (TraceKit, repro.obs).  Every layer of the
#    stack is instrumented behind a `tracer=None` no-op default:
#
#        PYTHONPATH=src python -m repro.launch.serve \
#            --quick --demo-adapters 2 --cache-bytes 16777216 \
#            --trace /tmp/serve.json
#
#    Load /tmp/serve.json at https://ui.perfetto.dev (or
#    chrome://tracing).  Lanes: one `tenant:<id>` row per adapter (and
#    `tenant:base`) holding each request's lifecycle — submit instant,
#    retroactive `queue_wait`, `prefill` chunks, `decode_step`s, and the
#    whole-`request` span; a `sched` row with `admit`, `swap_apply` /
#    `swap_revert` (delta row flips between tenants) and `jit_compile`
#    instants; a `cache` row with AdapterCache hits/promotions/
#    evictions/captures.  A `.jsonl` path writes the append-friendly
#    event log instead; `--metrics-every N` dumps the typed metrics
#    registry (decode/*, prefill/*, sched/*) as greppable text, and
#    `DecodeServer.stats()` returns the same numbers as nested
#    sections.  Training mirrors it: `launch.train --trace t.jsonl`
#    records per-step spans (data/step/ckpt/export lanes, the BlockLLM
#    step's phases inside `train_step`, and a `compile` lane) plus
#    BlockLLM selection telemetry per step — sel_q (selected fraction),
#    sel_churn (Jaccard distance between consecutive plans),
#    sel_grad_concentration (gradient-energy share of the selected
#    blocks), sel_steps_since_reselect.  Under `jax.profiler` the same
#    spans land in the profiler's host plane, and the device ops of the
#    train step carry the named scopes `blockllm_grad`,
#    `blockllm_grad_norms`, `blockllm_mask_refresh` and
#    `blockllm_update`: kernels are timed from that trace.  Traces are
#    CI-validated by tools/check_trace.py (the trace-smoke job);
#    benchmarks accept --trace-dir to emit one trace per measured leg.

# 7. Speculative serving (SpecServe).  Under BlockDelta a tenant IS the
#    base model plus <5% edited rows, so the base weights are always
#    resident — a free draft model.  `--speculate N` makes each decode
#    round flip the slot group to base weights, draft N tokens through
#    the normal fast decode path, flip back, then score all N+1
#    positions with the tenant's adapter in ONE chunked dispatch
#    (model.verify_into_slots):
#
#        PYTHONPATH=src python -m repro.launch.serve \
#            --quick --demo-adapters 1 --speculate 4 \
#            --trace /tmp/spec.json
#
#    The longest draft prefix agreeing with the verifier's greedy
#    argmaxes is accepted, plus the verifier's own next token (a bonus
#    on full accept, a correction on mismatch) — every emitted token is
#    an adapter argmax, so streams are BIT-IDENTICAL to plain decoding
#    by construction, dense or paged (rejected draft rows are masked
#    out by position dense-side and their pages unmapped paged-side).
#    Speedup == acceptance: a draft of 4 with acceptance rate `a` emits
#    ~(1 + 4a) tokens per round, so a near-base finetune (~0.85 on the
#    bench's repetitive text) decodes 3-5x fewer rounds, while a
#    divergent tenant degrades toward 1.0 — the per-group draft length
#    adapts automatically (halves under ~40% acceptance, regrows above
#    ~80%).  `DecodeServer.stats()["spec"]` reports rounds/drafted/
#    accepted/rollbacks/flips/acceptance_rate/tokens_per_step; traces
#    grow `spec_draft`/`spec_verify` spans (CI's trace-smoke validates
#    them via check_trace --require-spec) and the serve gate pins
#    spec_tokens_per_step / spec_acceptance_rate in
#    benchmarks/serve_baselines.json.

# 8. Fleet serving (FleetServe, runtime/fleet.py).  One DecodeServer is
#    one replica; `launch.fleet` puts N of them behind an
#    adapter-affinity router:
#
#        PYTHONPATH=src python -m repro.launch.fleet \
#            --quick --replicas 2 --demo-adapters 3 \
#            --cache-bytes 16777216 --trace /tmp/fleet.json
#
#    Tenants shard across replicas by consistent hashing (adding or
#    removing a replica remaps only ~1/N tenants, so HBM-resident
#    adapters mostly stay put).  Under load the router *spills* a hot
#    tenant to its ring successors (`--spill-depth`, default 2x batch
#    slots), *steals* queued work onto replicas that drained early, and
#    *sheds* requests whose `--slo-ms` no replica can meet — all driven
#    by the per-replica TraceKit observables.  When a tenant does land
#    on a second replica, its AdapterCache captures the first replica's
#    already-dequantized delta rows through the shared
#    FleetAdapterDirectory instead of re-reading disk (`peer_hits` /
#    `xrep_bytes`, zero host->device bytes).  Per-tenant token streams
#    stay bit-identical to single-replica serving (requests never split
#    across replicas and outputs are co-schedule-invariant).
#
#    The replication unit is a frozen ServeConfig
#    (runtime/serve_config.py): DecodeServer's ~15 flat kwargs folded
#    into one JSON-round-trippable tree (core + sched/kv/spec
#    sub-configs, `ServeConfig.from_json(cfg.to_json()) == cfg`).  Both
#    launchers share the flags: `--save-config fleet.json` writes the
#    resolved config, `--config fleet.json` reproduces the same server
#    shape; the flat DecodeServer kwargs still construct for one
#    release behind a DeprecationWarning.
#    `Router.stats()` returns a `fleet` roll-up (spills/steals/sheds,
#    tps_per_round, cross-replica bytes) + per-replica
#    DecodeServer.stats() + an aggregate metrics merge; `--trace` writes
#    ONE merged Perfetto trace with one process per replica plus the
#    router's route/steal/shed lane (CI validates it via
#    tools/check_trace.py --require-fleet).  benchmarks/bench_fleet.py
#    gates aggregate TPS >= 1.8x at 2 replicas on a Zipf mix (with
#    bit-identical streams) through the serve gate's fleet_* metrics.

# 9. Operating an elastic fleet (ElasticFleet, runtime/elastic.py).
#    Fleet membership is runtime-mutable and failure survivable:
#    `Router.add_replica()` grows the fleet live (the ring resize
#    remaps ~1/N tenants; their queued requests move over and their
#    HBM-resident delta rows are pre-captured device-to-device through
#    the FleetAdapterDirectory, zero h2d), `remove_replica()` shrinks
#    it losslessly (queued work re-routes to ring successors, in-flight
#    groups drain in place, resident rows hand off to each tenant's new
#    home).  `ReplicaHealth` generalizes runtime/straggler.py's
#    EMA/median rule to the serve side: a replica past `slow_threshold`
#    x the fleet-median step-time EMA is flagged a straggler (work
#    stealing rebalances it), one that makes no progress for
#    `wedge_rounds` rounds while holding work is **fenced** — off the
#    ring, queued requests re-routed (never shed), in-flight requests
#    *replayed* on peers from the retained prompt + already-streamed
#    tokens.  Greedy decode makes the replayed continuation
#    deterministic, and `Request.replay_clone` splices the clone's
#    stream back with watermark dedup, so consumers see every position
#    exactly once — bit-identical to a fault-free run.  Drill it with
#    deterministic fault injection:
#
#        PYTHONPATH=src python -m repro.launch.fleet \
#            --quick --replicas 2 --demo-adapters 3 \
#            --fault-plan "kill:replica1@round6" \
#            --replace-after-fence --assert-parity
#
#    (`wedge:replica0@round5`, `slow:replica1@round3:3x` and
#    `adapter_read_error:n=2` — transient registry read faults absorbed
#    by bounded retry-with-backoff — compose ';'-separated; seeded by
#    `--fault-seed`.)  `--assert-parity` re-serves the same requests
#    fault-free on one replica and hard-asserts stream equality; Ctrl-C
#    drains gracefully before flushing stats/traces.  SparseDelta
#    payloads are sealed with a SHA-256 checksum at save time and
#    verified on load (`AdapterCorruptError` on mismatch); the ring/
#    health/retry knobs live in `ServeConfig.fleet` (`FleetConfig`).
#    CI runs chaos-smoke (kill-and-replace + wedge-then-fence legs,
#    `check_trace --require-failover`), and the serve gate pins
#    fleet_recover_rounds / fleet_fault_shed from bench_fleet's
#    recovery leg.
