"""Serving driver — batched generation, optionally from a pruned+compressed
checkpoint.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --requests 8 --prompt-len 16 --max-new 12 --nm

``--full`` builds the config at its published widths instead of the toy
``REDUCED`` one (it needs an accelerator), e.g. on one TPU v5e:

    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
        --full --nm

``--nm`` prunes 2:4 with Thanos first and serves from the NmCompressed
representation (paper §4.8; HBM-traffic win quantified in
benchmarks/nm_decode_roofline.py).  ``--plan recipe.json`` prunes with a
``PrunePlan`` instead and serves with *per-layer residency*: paths whose
cell is n:m stay NmCompressed, everything else (unstructured cells, skip
rules) stays dense (DESIGN.md §11; try
examples/recipes/mixed_2to4_serve.json).

``--paged`` serves from the paged KV cache (DESIGN.md §12): slot rows
become shared page pools sized by ``--num-pages``, with prompt-prefix
reuse across requests.  ``--http`` starts the SSE streaming front-end
instead of the offline batch run and drives the same request mix over
HTTP with Poisson arrivals (``--deadline`` attaches per-request budgets).

``--supervise`` (implied by any of ``--fault-plan``, ``--snapshot-every``,
``--retry-budget``) wraps the engine in the fault supervisor
(DESIGN.md §13): periodic snapshots, rollback + bit-identical replay on
decode/prefill/pager faults, retry budgets with poison-request
quarantine.  ``--fault-plan`` arms a deterministic fault schedule — a
JSON file or the compact ``site@start[xburst][~uid][+payload]`` syntax,
e.g. ``--fault-plan 'decode_logits@5;pager_fault_in@9x8'``.
``--max-queued`` bounds admission (HTTP 503 + Retry-After past it) and
``--drain-timeout`` finishes in-flight requests at shutdown.
"""
from __future__ import annotations

import argparse
import asyncio
import time

import jax
import numpy as np

from repro.configs import registry
from repro.core import PruneConfig, PrunePlan
from repro.models.model_builder import build_model
from repro.serve import Request, ServeConfig, ServingEngine
from repro.serve.compressed import compress_params, compressed_bytes
from repro.util.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=list(registry.ARCHS))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--scheduler", default="continuous",
                    choices=["continuous", "wave"],
                    help="slot-level continuous batching (default) or the "
                         "legacy wave scheduler")
    ap.add_argument("--nm", action="store_true",
                    help="Thanos-prune 2:4 and serve compressed-resident")
    ap.add_argument("--plan", default="",
                    help="PrunePlan recipe: prune per-layer and serve with "
                         "mixed dense/NmCompressed residency")
    ap.add_argument("--nm-impl", default="",
                    choices=["", "auto", "ref", "pallas"],
                    help="compressed matmul impl (default: backend auto)")
    ap.add_argument("--nm-block-b", type=int, default=0)
    ap.add_argument("--nm-block-c", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache with prefix reuse (serve/pager.py)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per cache page (must divide max_len)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page pool size (0 = auto: full capacity)")
    ap.add_argument("--http", action="store_true",
                    help="serve over HTTP/SSE and drive the request mix as "
                         "a Poisson arrival trace against the live server")
    ap.add_argument("--http-port", type=int, default=0,
                    help="listen port (0 = ephemeral)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request deadline in seconds (0 = none)")
    ap.add_argument("--supervise", action="store_true",
                    help="wrap the engine in the fault supervisor "
                         "(serve/supervisor.py)")
    ap.add_argument("--fault-plan", default="",
                    help="arm a fault plan: JSON file path or compact "
                         "'site@start[xburst][~uid][+payload];…' spec "
                         "(implies --supervise)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="pumps between supervisor snapshots (0 = default; "
                         "> 0 implies --supervise)")
    ap.add_argument("--retry-budget", type=int, default=0,
                    help="faults a request survives before quarantine "
                         "(0 = default; > 0 implies --supervise)")
    ap.add_argument("--max-queued", type=int, default=0,
                    help="bound the request queue; past it submissions are "
                         "rejected (HTTP: 503 + Retry-After)")
    ap.add_argument("--full", action="store_true",
                    help="full config (needs real accelerators)")
    ap.add_argument("--drain-timeout", type=float, default=5.0,
                    help="seconds to finish in-flight requests at HTTP "
                         "shutdown (drain mode)")
    args = ap.parse_args()
    args.supervise = (args.supervise or bool(args.fault_plan)
                      or args.snapshot_every > 0 or args.retry_budget > 0)

    enable_compile_cache()
    cfg = registry.get_config(args.arch, reduced=not args.full)
    model = build_model(cfg)

    # prune_arch inits the same seed-0 params itself; the dense init below
    # is only built when nothing is pruned, so a full-width run never
    # holds two copies of the weights
    if args.plan:
        from repro.launch.prune import prune_arch

        plan = PrunePlan.load(args.plan)
        print(f"pruning with recipe {args.plan} ({len(plan.rules)} rules)…")
        pruned, report, _ = prune_arch(args.arch, plan,
                                       reduced=not args.full, log=None)
        params = compress_params(pruned, report.masks, plan=report.plan)
        comp, dense = compressed_bytes(params)
        if dense:
            print(f"compressed weight bytes: {comp / dense:.3f} of their "
                  f"dense bytes (non-n:m cells stay dense)")
        for row in report.rule_rollup():
            print(f"  rule {row['rule']:3d} {str(row['match']):20s} "
                  f"{row['tag']:18s} layers={row['layers']:3d} "
                  f"sparsity={row['mean_sparsity']:.3f}")
    elif args.nm:
        from repro.launch.prune import prune_arch

        print("pruning 2:4 with Thanos first…")
        pruned, report, _ = prune_arch(
            args.arch, PruneConfig(method="thanos", pattern="nm", n=2, m=4,
                                   block_size=64),
            reduced=not args.full, log=None,
        )
        params = compress_params(pruned, report.masks, 2, 4)
        comp, dense = compressed_bytes(params)
        if dense:
            print(f"compressed weight bytes: {comp / dense:.3f} of dense")
    else:
        params = model.init(jax.random.PRNGKey(0))

    max_len = args.prompt_len + args.max_new + 8
    if args.paged and max_len % args.page_size:
        max_len += args.page_size - max_len % args.page_size   # round up
    engine = ServingEngine(
        model, params,
        ServeConfig(batch_slots=args.slots,
                    max_len=max_len,
                    scheduler=("continuous"
                               if args.http or args.supervise
                               else args.scheduler),
                    nm_impl=args.nm_impl,
                    nm_block_b=args.nm_block_b,
                    nm_block_c=args.nm_block_c,
                    paged=args.paged,
                    page_size=args.page_size,
                    num_pages=args.num_pages,
                    max_queued=args.max_queued),
    )
    supervisor = _make_supervisor(engine, args) if args.supervise else None
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=args.prompt_len)
               for _ in range(args.requests)]

    if args.http:
        _serve_http(engine, args, prompts, supervisor)
        return

    runner = supervisor if supervisor is not None else engine
    for uid, prompt in enumerate(prompts):
        runner.submit(Request(uid, prompt, max_new=args.max_new,
                              deadline_s=args.deadline))
    t0 = time.perf_counter()
    done = runner.run()
    dt = time.perf_counter() - t0
    if supervisor is not None:
        st = supervisor.stats
        print(f"supervisor: state={supervisor.state} "
              f"recoveries={st['recoveries']} faults={st['faults']} "
              f"snapshots={st['snapshots']} "
              f"quarantined={supervisor.quarantined}")
    tokens = sum(len(r.out) for r in done)
    st = engine.stats
    occ = (st["busy_slot_steps"] / (st["decode_steps"] * args.slots)
           if st["decode_steps"] else 0.0)
    print(f"{len(done)} requests, {tokens} tokens in {dt:.2f}s "
          f"({tokens / dt:.1f} tok/s incl. compile; "
          f"{args.scheduler}: {st['decode_steps']} decode steps, "
          f"slot occupancy {occ:.2f})")
    if args.paged:
        print(f"  paged: hwm {st['pages_hwm']} pages of "
              f"{engine.pager.pool.num_pages - 1}, "
              f"{st['page_faults']} faults, {st['cow_copies']} COW, "
              f"{st['prefix_hit_tokens']} prefix-hit tokens, "
              f"{st['preemptions']} preemptions")
    for r in done[:4]:
        print(f"  req {r.uid}: {r.out}")


def _make_supervisor(engine, args):
    from repro.serve.faults import FaultPlan
    from repro.serve.supervisor import Supervisor, SupervisorConfig

    plan = FaultPlan.load(args.fault_plan) if args.fault_plan else None
    kw = {}
    if args.snapshot_every > 0:
        kw["snapshot_every"] = args.snapshot_every
    if args.retry_budget > 0:
        kw["retry_budget"] = args.retry_budget
    if plan is not None:
        print(f"fault plan armed: {len(plan.specs)} spec(s), "
              f"seed {plan.seed}")
    return Supervisor(engine, SupervisorConfig(**kw), faults=plan)


def _serve_http(engine, args, prompts, supervisor=None):
    """Start the SSE front-end and replay the mix with Poisson arrivals."""
    from repro.serve.frontend import HttpFrontend, drive_http_trace

    rng = np.random.default_rng(1)
    gaps = rng.exponential(scale=0.05, size=len(prompts))
    trace = [{"uid": i, "t": float(gaps[:i + 1].sum()), "prompt": p,
              "max_new": args.max_new, "deadline_s": args.deadline}
             for i, p in enumerate(prompts)]

    async def main():
        fe = HttpFrontend(engine, supervisor=supervisor, port=args.http_port)
        await fe.start()
        print(f"SSE front-end on http://127.0.0.1:{fe.port} — replaying "
              f"{len(trace)} Poisson arrivals…")
        t0 = time.perf_counter()
        results = await drive_http_trace("127.0.0.1", fe.port, trace)
        dt = time.perf_counter() - t0
        drained = await fe.stop(drain_timeout_s=args.drain_timeout)
        tokens = sum(len(r["tokens"]) for r in results)
        errors = [r["final"].get("error") for r in results
                  if r["final"].get("error")]
        print(f"{len(results)} streams, {tokens} tokens in {dt:.2f}s "
              f"({tokens / dt:.1f} tok/s over HTTP incl. compile; "
              f"{len(errors)} errored: {errors[:4]}; "
              f"drained={'yes' if drained else 'timeout'})")
        if supervisor is not None:
            st = supervisor.stats
            print(f"supervisor: state={supervisor.state} "
                  f"recoveries={st['recoveries']} faults={st['faults']} "
                  f"quarantined={supervisor.quarantined}")
        for r in results[:4]:
            print(f"  req {r['uid']}: {r['tokens']}")

    asyncio.run(main())


if __name__ == "__main__":
    main()
