"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

End-to-end launcher: spins up the Engine on a registered config (``--preset
full`` keeps its published widths, ``tiny``/``100m`` shrink it for the CPU),
submits a batch of synthetic requests, and reports latency/throughput with ISO
on vs off — the paper's experiment shape.  The persistent compilation cache
is turned on before the first compile (launch/compile_cache.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import Config, ISOConfig, ParallelConfig, RuntimeConfig, \
    ServingConfig, get_model_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import reduce_cfg
from repro.models import api
from repro.serving import Engine, PagedEngine, Request
from repro.serving.requests import SamplingParams


def _drive(eng, args):
    """Step the engine to completion, printing a one-line metrics summary
    every ``--metrics-every`` steps (the dense and paged engines share the
    loop; pool columns are paged-only)."""
    if hasattr(eng, "prefill"):               # DisaggRouter: two engines
        return _drive_disagg(eng, args)
    if not args.metrics_every:
        return eng.run_until_complete()
    paged = hasattr(eng, "alloc")
    t_last = time.perf_counter()
    toks_last = 0
    for _ in range(10_000):
        eng.step()
        waiting = eng.scheduler.waiting if paged else eng.pending
        done = not waiting and all(s is None for s in eng.slots)
        m = eng.metrics
        if m["steps"] % args.metrics_every == 0 or done:
            now = time.perf_counter()
            toks = m["decode_tokens"] + m["prefill_samples"]
            rate = (toks - toks_last) / max(now - t_last, 1e-9)
            t_last, toks_last = now, toks
            active = sum(s is not None for s in eng.slots)
            line = (f"[metrics] step={m['steps']} active={active} "
                    f"waiting={len(waiting)} tok/s={rate:.1f}")
            if paged:
                line += (f" pool={eng.alloc.used_pages}/"
                         f"{eng.alloc.num_pages}"
                         f" frag={eng.alloc.fragmentation()}")
                if eng.spec_k:
                    line += f" accept/call={eng.accepted_per_call():.2f}"
            print(line)
        if done:
            break
    out = {}
    for st in eng._finished:
        out[st.request.rid] = st.generated
    return out


def _drive_disagg(router, args):
    """Step the router to completion; per-phase metrics lines on request."""
    if not args.metrics_every:
        return router.run_until_complete()
    t_last = time.perf_counter()
    toks_last = steps = 0
    for _ in range(10_000):
        router.step()
        steps += 1
        done = router.done()
        if steps % args.metrics_every == 0 or done:
            pm = router.prefill.metrics
            dm = router.decode.metrics
            now = time.perf_counter()
            toks = dm["decode_tokens"] + pm["prefill_samples"]
            rate = (toks - toks_last) / max(now - t_last, 1e-9)
            t_last, toks_last = now, toks
            ms = router.migration_stats()
            print(f"[metrics] step={steps} "
                  f"prefill_active="
                  f"{sum(s is not None for s in router.prefill.slots)} "
                  f"decode_active="
                  f"{sum(s is not None for s in router.decode.slots)} "
                  f"waiting={len(router.prefill.scheduler.waiting)} "
                  f"tok/s={rate:.1f} "
                  f"migrated={ms['migrated_requests']} "
                  f"deferrals={ms['deferrals']}")
        if done:
            break
    out = {}
    for st in router.prefill._finished + router.decode._finished:
        out[st.request.rid] = st.generated
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="tiny", choices=["tiny", "100m", "full"])
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--iso-off", action="store_true")
    ap.add_argument("--chunks", type=int, default=2)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache + chunked-prefill scheduler")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-budget", type=int, default=64)
    ap.add_argument("--policy", default="fcfs", choices=["fcfs", "priority"])
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: the paged engine runs under "
                         "shard_map over a (1, tp) mesh (needs tp devices; on "
                         "CPU export XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=<tp>)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common system prompt of N tokens to every "
                         "request (exercises CoW prefix/page sharing)")
    ap.add_argument("--no-prefix-sharing", action="store_true")
    ap.add_argument("--no-batched-prefill", action="store_true",
                    help="run prefill grants batch-1 (one forward call per "
                         "grant) instead of packing same-bucket grants into "
                         "one batched call per scheduler tick")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving: one prefill engine + one "
                         "decode engine, requests migrate by KV-page "
                         "transfer the moment their prompt is resident "
                         "(serving/disagg.py; with --tp N the two engines "
                         "run on disjoint N-device meshes — needs 2N "
                         "devices)")
    ap.add_argument("--decode-pool-pages", type=int, default=0,
                    help="decode-side page-pool size under --disagg "
                         "(0 = same as the prefill pool); a full decode "
                         "pool defers migration, it never drops requests")
    ap.add_argument("--migrate-batch", type=int, default=0,
                    help="max requests migrated per router step under "
                         "--disagg (0 = all that fit); batched transfers "
                         "keep CoW page sharing across the move")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: verify a (k+1)-token "
                         "self-drafted window per decode step (greedy only; "
                         "paged engine runs it through the flash-decode "
                         "kernel, dense engine through the padded cache)")
    ap.add_argument("--cost-table", default="", metavar="PATH|auto",
                    help="measured cost model (perf/costmodel.py): 'auto' "
                         "loads the bundled per-platform table under "
                         "src/repro/perf/tables/, a path loads that table; "
                         "the engine/scheduler then CHOOSE split counts, "
                         "chunk sizes, pack widths and the spec gate from "
                         "measurements (any load failure falls back to "
                         "static defaults with one warning trace event)")
    ap.add_argument("--autotune", action="store_true",
                    help="before serving, profile this machine (smoke "
                         "sweeps) and serve with the resulting cost model "
                         "(ignores --cost-table); write a persistent table "
                         "with benchmarks/autotune.py instead")
    ap.add_argument("--trace-out", default=None, metavar="trace.json",
                    help="export the engine's structured trace as Chrome-"
                         "trace JSON (open at https://ui.perfetto.dev); with "
                         "--jax-profile its times are on the profile's clock")
    ap.add_argument("--metrics-every", type=int, default=0, metavar="N",
                    help="print a one-line metrics summary every N engine "
                         "steps (active slots, pool occupancy, tok/s, "
                         "accept rate)")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the run into DIR "
                         "(TensorBoard/Perfetto; the engine's phase spans "
                         "and closure dispatches are TraceAnnotation'd)")
    ap.add_argument("--probe-overlap", action="store_true",
                    help="after the run, measure decode overlap efficiency "
                         "per collective schedule (sequential vs batch-split "
                         "vs ladder vs cross-block on identical synthetic "
                         "batches; paged engine only)")
    ap.add_argument("--decode-schedule", default="auto",
                    choices=["auto", "sequential", "batch_split",
                             "cross_block"],
                    help="decode collective schedule (core/iso.py): auto "
                         "picks batch_split when the mesh + batch allow it; "
                         "cross_block defers every all-reduce to the next "
                         "stage top (token-identical; pays off with "
                         "--latency-hiding).  Ladder wiring is an ARCH "
                         "(--arch ladder-qwen3-4b ...), not a schedule flag")
    ap.add_argument("--latency-hiding", action="store_true",
                    help="set the async-collective XLA flags "
                         "(launch/mesh.LATENCY_HIDING_XLA_FLAGS) before "
                         "backend init so the latency-hiding scheduler can "
                         "fill the deferred-collective windows the "
                         "cross_block/ladder schedules open")
    args = ap.parse_args(argv)
    if args.latency_hiding:
        # must land in XLA_FLAGS before the first backend touch below
        # (jax.random.PRNGKey init); mesh.py keeps imports side-effect-free
        # precisely so this ordering works
        from repro.launch.mesh import enable_latency_hiding
        if enable_latency_hiding():
            print("[xla] async-collective latency-hiding flags enabled")
    if args.probe_overlap and not args.paged:
        ap.error("--probe-overlap requires --paged")
    if (args.autotune or args.cost_table) and not args.paged:
        ap.error("--autotune/--cost-table require --paged (the dense Engine "
                 "has no cost-model decision points)")
    if args.spec_k and args.temperature > 0:
        ap.error("--spec-k is greedy-only (needs --temperature 0)")
    if args.disagg and not args.paged:
        ap.error("--disagg requires --paged (migration moves KV pages)")
    if args.disagg and (args.probe_overlap or args.autotune):
        ap.error("--disagg does not combine with --probe-overlap/--autotune")
    if (args.decode_pool_pages or args.migrate_batch) and not args.disagg:
        ap.error("--decode-pool-pages/--migrate-batch require --disagg")

    cfg = reduce_cfg(get_model_config(args.arch), args.preset)
    if args.paged and cfg.family == "audio":
        ap.error("--paged does not support enc-dec (audio) archs yet")
    if args.tp > 1 and not args.paged:
        ap.error("--tp requires --paged (the dense Engine stays single-device)")
    iso = ISOConfig(enabled=not args.iso_off, num_chunks=args.chunks,
                    min_chunk_tokens=16, chunk_align=16)
    max_len = args.shared_prefix + args.prompt_len + args.max_new + 8
    max_len = max_len + (args.spec_k + 1 if args.spec_k else 0)
    serving = ServingConfig(page_size=args.page_size, max_batch=args.max_batch,
                            max_len=max_len,
                            prefill_token_budget=args.prefill_budget,
                            scheduler_policy=args.policy,
                            prefix_sharing=not args.no_prefix_sharing,
                            prefill_batching=not args.no_batched_prefill,
                            spec_k=args.spec_k,
                            cost_table="" if args.autotune
                            else args.cost_table,
                            disagg=args.disagg,
                            decode_pool_pages=args.decode_pool_pages,
                            migrate_batch=args.migrate_batch,
                            decode_schedule=args.decode_schedule,
                            latency_hiding=args.latency_hiding)
    config = Config(model=cfg, parallel=ParallelConfig(data=1, model=args.tp),
                    iso=iso, runtime=RuntimeConfig(mode="serve"),
                    serving=serving)
    enable_compile_cache()
    mesh = None
    if args.paged and args.tp > 1 and not args.disagg:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(config.parallel)
    key = jax.random.PRNGKey(0)
    # under a TP mesh every device builds only its own parameter shard
    params = api.init_params(key, cfg, tp=args.tp, mesh=mesh)
    if args.paged:
        if args.autotune:
            # in-process profile of THIS machine/mesh (smoke sweeps), then
            # serve with the resulting model injected
            import dataclasses

            from repro.perf.costmodel import CostModel, autotune
            print("[autotune] profiling (smoke sweeps)...")
            table = autotune(config, params, mesh=mesh, smoke=True,
                             log=lambda msg: print(f"[autotune] {msg}"))
            config = config.replace(serving=dataclasses.replace(
                serving, cost_model=CostModel(table)))
        if args.disagg:
            from repro.serving.disagg import DisaggRouter
            pmesh = dmesh = None
            if args.tp > 1:
                from repro.launch.mesh import disagg_meshes
                pmesh, dmesh = disagg_meshes(config.parallel)
            eng = DisaggRouter(config, params, prefill_mesh=pmesh,
                               decode_mesh=dmesh)
        else:
            eng = PagedEngine(config, params, mesh=mesh)
        if not args.disagg and eng.cost_model is not None:
            print(f"[costmodel] active: platform={eng.cost_model.platform} "
                  f"tp={eng.cost_model.tp} "
                  f"alpha={eng.cost_model.alpha_s:.3e}s "
                  f"beta={eng.cost_model.beta_s_per_byte:.3e}s/B")
    else:
        eng = Engine(config, params, mesh=None, max_batch=args.max_batch,
                     max_len=max_len, bucket=32, spec_k=args.spec_k)

    rng = np.random.default_rng(0)
    system = rng.integers(2, cfg.vocab_size,
                          args.shared_prefix).astype(np.int32)
    t0 = time.perf_counter()
    for i in range(args.requests):
        plen = int(rng.integers(args.prompt_len // 2, args.prompt_len))
        prompt = rng.integers(2, cfg.vocab_size, plen).astype(np.int32)
        if args.shared_prefix:
            prompt = np.concatenate([system, prompt])
        req = Request(
            prompt=prompt,
            sampling=SamplingParams(max_new_tokens=args.max_new, eos_id=-1,
                                    temperature=args.temperature, seed=i))
        if cfg.family == "audio":
            req.frames = (rng.standard_normal(
                (cfg.encoder_frames, cfg.d_model)) * 0.1).astype(np.float32)
        if cfg.family == "vlm":
            req.patches = (rng.standard_normal(
                (cfg.num_patches, cfg.d_model)) * 0.1).astype(np.float32)
        eng.add_request(req)
    if args.jax_profile:
        from repro.obs import jaxprof
        jaxprof.start(args.jax_profile)
    outs = _drive(eng, args)
    wall = time.perf_counter() - t0
    anchor = None
    if args.jax_profile:
        from repro.obs import jaxprof
        jaxprof.stop()
        # with --trace-out too: put the ring's events on the profile's clock
        # so the two files overlay (docs/observability.md)
        anchor = jaxprof.clock_anchor(args.jax_profile)

    total_new = sum(len(v) for v in outs.values())
    if args.disagg:
        pm, dm = eng.prefill.metrics, eng.decode.metrics
        ms = eng.migration_stats()
        ttft = pm["ttft_sum"] / max(pm["ttft_n"], 1)
        tpot = eng.decode.registry.histogram("tpot")
        print(f"arch={cfg.name} iso={'off' if args.iso_off else 'on'} "
              f"disagg requests={len(outs)} new_tokens={total_new} "
              f"wall={wall:.2f}s")
        print(f"prefill phase: {pm['prefill_tokens']} tok in "
              f"{pm['prefill_s']:.2f}s calls={pm['prefill_calls']} "
              f"grants={pm['prefill_grants']} ttft={ttft * 1e3:.1f}ms")
        print(f"decode phase: {dm['decode_tokens']} tok in "
              f"{dm['decode_s']:.2f}s calls={dm['decode_calls']} "
              f"tpot={tpot.mean * 1e3:.2f}ms "
              f"preemptions={dm['preemptions']}")
        print(f"migration: transfers={ms['migrations']} "
              f"requests={ms['migrated_requests']} "
              f"pages={ms['migrated_pages']} "
              f"us={ms['migration_us']:.0f} "
              f"deferrals={ms['deferrals']} "
              f"bounce_backs={ms['bounce_backs']}")
        if args.trace_out:
            from repro.obs import write_chrome_trace
            ev = eng.prefill.trace.events() + eng.decode.trace.events()
            n = write_chrome_trace(ev, args.trace_out, anchor=anchor)
            print(f"trace: {n} events -> {args.trace_out} (both engines)")
        for rid in sorted(outs)[:3]:
            print(f"  rid {rid}: {outs[rid][:10]}"
                  f"{'...' if len(outs[rid]) > 10 else ''}")
        return 0
    m = eng.metrics
    print(f"arch={cfg.name} iso={'off' if args.iso_off else 'on'} "
          f"requests={len(outs)} new_tokens={total_new} wall={wall:.2f}s")
    print(f"prefill: {m['prefill_tokens']} tok in {m['prefill_s']:.2f}s | "
          f"decode: {m['decode_s']:.2f}s | completed={m['completed']}")
    if args.paged:
        s = eng.page_stats()
        ttft = m["ttft_sum"] / max(m["ttft_n"], 1)
        print(f"paged: steps={m['steps']} prefill_calls={m['prefill_calls']} "
              f"prefill_grants={m['prefill_grants']} "
              f"preemptions={m['preemptions']} ttft={ttft * 1e3:.1f}ms | "
              f"pages={s['num_pages']}x{s['page_size']} "
              f"kv_reserved={s['kv_bytes_reserved']}B tp={args.tp}")
        print(f"sharing: shared_tokens={m['prefix_shared_tokens']} "
              f"cow_copies={m['cow_copies']} "
              f"peak_pages={m['peak_used_pages']}")
        if args.autotune or args.cost_table:
            ev = eng.trace.events()
            dec = sum(1 for e in ev if e.kind == "decision")
            warn = sum(1 for e in ev if e.kind == "warning")
            print(f"costmodel: decisions={dec} warnings={warn} "
                  f"(see --trace-out for per-decision detail)")
        if args.spec_k:
            print(f"speculative: spec_k={args.spec_k} "
                  f"verify_calls={m['spec_calls']} "
                  f"accepted_per_call={eng.accepted_per_call():.2f} "
                  f"decode_tokens={m['decode_tokens']}")
    elif args.spec_k:
        print(f"speculative: spec_k={args.spec_k} "
              f"extra_accepted={m['spec_accepted']} "
              f"decode_calls={m['decode_calls']} "
              f"decode_tokens={m['decode_tokens']}")
    if args.probe_overlap:
        res = eng.measure_overlap_efficiency()
        exp = res["exposed_comm_s"]
        print(f"overlap probe: efficiency={res['overlap_efficiency']:.3f} "
              f"ladder_speedup={res['ladder_speedup']:.3f}"
              f"{' (proxy)' if res['ladder_proxy'] else ''} "
              f"exposed_comm="
              f"{'n/a' if exp is None else f'{exp * 1e3:.2f}ms'} "
              f"(tp={res['tp']}, B={res['batch']})")
        for name, t in sorted(res["schedules"].items()):
            print(f"  schedule {name:<12} {t * 1e3:.2f} ms/step")
    if args.trace_out:
        from repro.obs import write_chrome_trace
        n = write_chrome_trace(eng.trace.events(), args.trace_out,
                               anchor=anchor)
        print(f"trace: {n} events -> {args.trace_out} "
              f"(dropped={eng.trace.dropped}; "
              f"{'on the profile clock; ' if anchor else ''}"
              f"open at https://ui.perfetto.dev)")
    for rid in sorted(outs)[:3]:
        print(f"  rid {rid}: {outs[rid][:10]}{'...' if len(outs[rid]) > 10 else ''}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
