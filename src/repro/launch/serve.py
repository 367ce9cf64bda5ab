"""Serving launcher: batched generation with the Engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --batch 4 --prompt-len 16 --new 32

Traffic-shaped mode: ``--arrival-rate R`` switches from one batched
``generate`` call to the continuous-batching scheduler — a synthetic
arrival trace (``--requests N`` requests; geometric inter-arrival gaps for
R in (0,1], Bernoulli-packed overload arrivals for R > 1) drains through
``Engine.serve_stream`` with ``--max-slots`` decode lanes (default
``--batch``, the warmed plan bucket), printing tokens/s, slot occupancy,
queue waits and per-request TTFT.  See docs/serving.md "Continuous
batching".

Overload controls (docs/serving.md "Overload behavior"):
``--prefill-chunk-tokens`` bounds per-step prefill work,
``--preempt longest_remaining|lowest_priority`` enables slot preemption,
``--max-queue`` bounds the admission queue (overflow shed as
``queue_full``), and ``--deadline-ms`` attaches a completion deadline to
every synthetic request and turns on deadline-aware shedding.

Observability: ``--trace out.json`` records a Chrome-trace of the whole run
(warmup → prefill → per-token decode; open at https://ui.perfetto.dev),
``--metrics`` prints the unified metrics snapshot (plan-registry hit rates,
emission-tier mix, latency percentiles), ``--profile DIR`` brackets the
generate call with a ``jax.profiler`` capture.  See docs/observability.md.
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import load_arch
from repro.launch import compile_cache
from repro.models import model as model_mod
from repro.serve.engine import Engine, ServeConfig


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--attention-impl", default=None,
                    help="override cfg.attention_impl (xla_chunked|pallas)")
    ap.add_argument("--ssm-impl", default=None,
                    help="override cfg.ssm_impl (xla|pallas)")
    ap.add_argument("--kernel-plan", default=None,
                    help="override cfg.kernel_plan (measure|direct)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the plan-registry bucket-grid warmup")
    ap.add_argument("--plan-artifact", default=None, metavar="PATH",
                    help="warm-start from a published plan artifact "
                         "(python -m repro.launch tune): verified entries "
                         "replay with zero autotune measurements; "
                         "rejected/missing entries re-measure locally")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    metavar="R",
                    help="traffic-shaped mode: drain a synthetic arrival "
                         "trace through the continuous-batching scheduler "
                         "(geometric gaps for R in (0,1]; R > 1 packs "
                         "overload arrivals)")
    ap.add_argument("--max-slots", type=int, default=None,
                    help="decode lanes for --arrival-rate mode "
                         "(default: --batch, the warmed plan bucket)")
    ap.add_argument("--requests", type=int, default=8,
                    help="requests in the --arrival-rate trace")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=None,
                    metavar="T",
                    help="chunked prefill: cap per-step prefill work at T "
                         "tokens (long prompts admit over several steps)")
    ap.add_argument("--preempt", default=None, metavar="POLICY",
                    choices=("longest_remaining", "lowest_priority"),
                    help="enable slot preemption under queue pressure "
                         "(longest_remaining|lowest_priority)")
    ap.add_argument("--max-queue", type=int, default=None, metavar="N",
                    help="bound the admission queue at N; overflow is shed "
                         "with reason queue_full")
    ap.add_argument("--deadline-ms", type=float, default=None, metavar="MS",
                    help="attach a MS deadline to every synthetic request "
                         "and shed provably-unmeetable ones")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of the run to PATH")
    ap.add_argument("--metrics", action="store_true",
                    help="print the full metrics snapshot after the run")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of generate() to DIR")
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.trace:
        obs.enable()

    cfg = load_arch(args.arch, smoke=args.smoke)
    overrides = {k: v for k, v in (("attention_impl", args.attention_impl),
                                   ("ssm_impl", args.ssm_impl),
                                   ("kernel_plan", args.kernel_plan)) if v}
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32 if args.smoke
                                   else jnp.bfloat16)
    scfg = ServeConfig(batch=args.batch,
                       max_len=args.prompt_len + args.new + 1,
                       temperature=args.temperature,
                       warmup=not args.no_warmup,
                       plan_artifact=args.plan_artifact)
    eng = Engine(cfg, params, scfg)
    if eng.artifact_report is not None:
        a = eng.artifact_report
        if "error" in a:
            print(f"[serve] plan artifact UNREADABLE ({a['error']}) — "
                  f"tuning locally")
        else:
            print(f"[serve] plan artifact: {a['verified']}/{a['total']} "
                  f"entr(ies) verified, {a['rejected']} rejected"
                  + (f" ({a['reasons']})" if a["rejected"] else "")
                  + (f", {a['missing']} unmeasured upstream"
                     if a["missing"] else ""))
    prof = (obs.profile("serve.generate", logdir=args.profile)
            if args.profile else contextlib.nullcontext())

    if args.arrival_rate is not None:
        # traffic-shaped mode: synthetic arrivals through the scheduler
        if cfg.family == "encdec":
            ap.error("--arrival-rate mode needs a decoder cache "
                     "(encdec archs are not supported by the scheduler)")
        from repro.serve import scheduler as sched_mod
        reqs = sched_mod.synthetic_workload(
            args.requests, seed=1,
            prompt_lens=(max(1, args.prompt_len // 2), args.prompt_len),
            new_tokens=(args.new,), arrival_rate=args.arrival_rate,
            vocab=cfg.vocab_size,
            deadlines_ms=((args.deadline_ms,)
                          if args.deadline_ms is not None else None))
        occ = []
        t0 = time.time()
        with prof:
            results, shed = eng.serve_stream(
                reqs, max_slots=args.max_slots,
                step_hook=lambda s: occ.append(s["occupancy"]),
                prefill_chunk_tokens=args.prefill_chunk_tokens,
                preempt_policy=args.preempt,
                max_queue=args.max_queue,
                deadline_aware=args.deadline_ms is not None,
                return_shed=True)
        dt = time.time() - t0
        total_new = sum(r.n_new for r in reqs)
        served_new = sum(len(r.tokens) for r in results) if results else 0
        ttft = sorted(r.ttft_s for r in results) or [float("nan")]
        waits = [r.queue_wait_steps for r in results] or [0]
        n_deg = sum(1 for r in results if r.degraded)
        print(f"[serve] streamed {len(results)}/{len(reqs)} requests "
              f"({served_new}/{total_new} new tokens) in {dt:.2f}s wall "
              f"— {served_new / dt:.1f} tok/s at rate "
              f"{args.arrival_rate}")
        print(f"[serve] slots: peak occupancy {max(occ, default=0)}/"
              f"{args.max_slots or args.batch} over {len(occ)} steps; "
              f"queue wait: max {max(waits)} step(s); "
              f"ttft p50 {ttft[len(ttft) // 2] * 1e3:.1f}ms")
        n_pre = sum(r.preemptions for r in results)
        if n_pre:
            print(f"[serve] preemptions: {n_pre} across "
                  f"{sum(1 for r in results if r.preemptions)} request(s) "
                  f"(policy {args.preempt})")
        if shed:
            reasons: dict = {}
            for s in shed:
                reasons[s.reason] = reasons.get(s.reason, 0) + 1
            detail = ", ".join(f"{k}={v}" for k, v in sorted(reasons.items()))
            print(f"[serve] SHED: {len(shed)}/{len(reqs)} request(s) "
                  f"rejected by admission control ({detail})")
        if n_deg:
            print(f"[serve] DEGRADED: {n_deg} request(s) re-served off "
                  f"the planned path")
        out = None
    else:
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size)
        enc_out = None
        if cfg.family == "encdec":
            from repro.models import encdec
            frames = jax.random.normal(
                jax.random.PRNGKey(2),
                (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
            enc_out = encdec.encode(cfg, params, frames)
        t0 = time.time()
        with prof:
            out = eng.generate(prompts, args.new, enc_out=enc_out)
        dt = time.time() - t0

    stats = eng.stats()
    dec = stats["phases"].get("decode", {})
    pre = stats["phases"].get("prefill", {})
    steady = dec.get("steady_mean_s")
    if out is not None:
        # steady-state tok/s excludes warmup + compile (first prefill/
        # decode): measured-pump wins are a steady-state property, and one
        # cold compile can be 1000x a decode step
        tps = args.batch / steady if steady else float("nan")
        print(f"[serve] generated {out.shape} in {dt:.2f}s wall")
        print(f"[serve] warmup: {stats['warmup_s']:.2f}s "
              f"({stats['plans_warmed']} plans warmed, "
              f"{stats['warmup_measured']} freshly measured); "
              f"compile: prefill {pre.get('compile_s', 0):.2f}s, "
              f"decode {dec.get('compile_s', 0):.2f}s")
        for line in obs.format_phases(stats["phases"]).splitlines():
            print(f"[serve] {line}")
        print(f"[serve] steady-state decode: "
              f"{(steady or float('nan')) * 1e3:.2f} ms/step mean "
              f"({tps:.1f} tok/s)")
    if stats["registry"] is not None:
        # prefill vs decode bucket split: a cold decode bucket (misses > 0
        # after warmup) must be visible at a glance, not buried in a total
        r = stats["registry"]
        print(f"[serve] plan registry: prefill {r['prefill']} | "
              f"decode {r['decode']} | hit_rate={r['hit_rate']} "
              f"fallbacks={r['fallbacks']} measure_s={r['measure_s']}")
    # robustness surface (docs/robustness.md): degraded requests, failed
    # warmup buckets and quarantined plans all say "the ladder was walked" —
    # zero on a healthy run, and a loud launch-output line when not
    from repro.compiler import default_cache
    quarantined = default_cache().quarantine_entries()
    if (stats["degraded_requests"] or stats["warmup_failed"]
            or quarantined):
        print(f"[serve] DEGRADED: {stats['degraded_requests']} request(s) "
              f"served off the planned path, {stats['warmup_failed']} "
              f"warmup bucket(s) failed, {len(quarantined)} plan(s) "
              f"quarantined")
        for key, q in sorted(quarantined.items()):
            print(f"[serve]   quarantine {key[:20]}…: {q['reason']} "
                  f"(fail #{q['fails']})")
    if out is not None:
        print("[serve] first sequence:", out[0][:16].tolist())
    else:
        first = min(results, key=lambda r: r.rid)
        print("[serve] first request tokens:",
              [int(t) for t in first.tokens[:16]])

    if args.metrics:
        for line in obs.format_snapshot(obs.snapshot()).splitlines():
            print(f"[metrics] {line}")
    if args.trace:
        obs.write_trace(args.trace,
                        metadata={"arch": args.arch, "batch": args.batch,
                                  "prompt_len": args.prompt_len,
                                  "n_new": args.new})
        print(f"[serve] trace written to {args.trace} "
              f"(open at https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
