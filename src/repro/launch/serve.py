"""End-to-end serving driver (the paper is a serving paper, so this is
the primary launcher): train-or-load a model, stand up the unified
``repro.serving.api.Server`` with the closed-loop controller plugged in
as admission middleware, replay a workload on the chosen execution
path, and log latency/throughput/energy/CO2 to the tracker.

All four paths go through one ``Server.serve(requests)`` call:

    PYTHONPATH=src python -m repro.launch.serve \
        --requests 2000 --qps 150 --controller bio --path auto
    PYTHONPATH=src python -m repro.launch.serve --controller open ...
    PYTHONPATH=src python -m repro.launch.serve --path gated \
        --requests 512                  # in-graph admission, live model
    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b \
        --mode generate --requests 4    # continuous-decode (smoke cfg)

``--fleet`` switches to the multi-replica layer (``repro.fleet``): a
heterogeneous replica pool, a routing policy, an optional autoscaler,
and a traffic scenario — the ORT-vs-Triton boundary as a runtime
decision:

    PYTHONPATH=src python -m repro.launch.serve --fleet
    PYTHONPATH=src python -m repro.launch.serve --fleet \
        --scenario diurnal --policy round-robin --no-autoscale
    PYTHONPATH=src python -m repro.launch.serve --fleet \
        --fleet-kinds direct,direct,dynamic-batch,continuous-decode

``--fleet-live`` swaps the oracle-backed virtual-time replicas for the
LIVE engine adapters (real jit'd models, measured walltimes) — the
same router/autoscaler/scenario machinery over real execution:

    PYTHONPATH=src python -m repro.launch.serve --fleet-live \
        --requests 200 --max-batch 8 --policy energy-aware

``--fleet-disagg`` runs a generate scenario over the disaggregated
prefill/decode fleet (``repro.disagg``): separate phase pools over one
LM weight copy, a modelled KV transfer link, phase-aware routing, and
an autoscaler per phase:

    PYTHONPATH=src python -m repro.launch.serve --fleet-disagg \
        --scenario prompt-burst --requests 48
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import ARCH_IDS, ModelConfig, get_smoke_config
from repro.core import (AdaptiveThreshold, AdmissionController,
                        CostWeights, DecayingThreshold, LatencyModel)
from repro.models import distilbert
from repro.models import transformer as tfm
from repro.serving import (AdmissionMiddleware, ClassifierEngine,
                           ContinuousBatchingEngine,
                           ContinuousEngineAdapter, DirectPath,
                           DynamicBatcher, GatedEngineAdapter,
                           InferRequest, Oracle, OracleEngine, Server,
                           ServerConfig, TelemetryMiddleware,
                           bursty_arrivals, canonical_path,
                           poisson_arrivals)
from repro.launch.compile_cache import enable_compilation_cache
from repro.telemetry import (NULL_METRICS, NULL_TRACER, CarbonTracker,
                             CompileWatcher, EnergyDriftAudit,
                             MetricsRegistry, Tracer, Tracker,
                             export_observability, make_measured_source,
                             validate_trace)
from repro.training import ClassificationData, train_classifier


def make_observability(args):
    """Tracer / metrics / drift-audit kit for one serving run.

    Real recorders only when ``--trace-out``/``--metrics-out`` asked
    for exports — the default stays the no-op fast path so untraced
    runs pay nothing.  The drift audit starts its measured-energy
    window immediately."""
    if not (getattr(args, "trace_out", None)
            or getattr(args, "metrics_out", None)):
        return NULL_TRACER, NULL_METRICS, None
    audit = EnergyDriftAudit(
        source=make_measured_source(args.energy_source)).start()
    # compile-time visibility: xla.compile spans + the compile_seconds
    # gauge (0.0 on a warm start) — how cache hits show up in metrics
    args._compile_watch = CompileWatcher().install()
    return Tracer(), MetricsRegistry(), audit


def finish_observability(args, run, tracer, metrics, audit, *,
                         modelled_j: float = 0.0,
                         n_requests: int = 0) -> dict:
    """Close the drift window, land artifacts beside the run's CSVs,
    and write the ``--trace-out``/``--metrics-out`` files.  Returns the
    drift report (empty when observability is off)."""
    import os
    import sys

    if audit is None:
        return {}
    audit.record(modelled_j, n_requests)
    report = audit.stop()
    if metrics.enabled:
        audit.export(metrics)
    watcher = getattr(args, "_compile_watch", None)
    if watcher is not None:
        watcher.export(tracer, metrics)
    if run is not None:
        export_observability(run, tracer=tracer, metrics=metrics,
                            audit=audit)
    if getattr(args, "trace_out", None) and tracer.enabled:
        problems = validate_trace(tracer.spans)
        if problems:       # keep the artifact; CI's validator decides
            print("trace audit: " + "; ".join(problems[:5]),
                  file=sys.stderr)
        tracer.write_chrome(args.trace_out)
    if getattr(args, "metrics_out", None) and metrics.enabled:
        metrics.write_json(args.metrics_out)
        metrics.write_prometheus(
            os.path.splitext(args.metrics_out)[0] + ".prom")
    return report


def build_classifier(seed: int = 0, steps: int = 150):
    cfg = distilbert.config(n_layers=3, d_model=64, n_heads=4, d_ff=128,
                            vocab=600, max_pos=48)
    params = distilbert.init(cfg, jax.random.PRNGKey(seed))
    data = ClassificationData(vocab=600, seq_len=32, seed=seed + 1)
    params, _ = train_classifier(cfg, params, data.train_batches(32),
                                 steps=steps, verbose=False)
    return cfg, params, data


def make_controller(kind: str, *, weights: str, target_rate: float):
    w = {"balanced": CostWeights(),
         "performance": CostWeights.performance_priority(),
         "ecology": CostWeights.ecology_priority()}[weights]
    if kind == "open":
        return AdmissionController(enabled=False)
    if kind == "adaptive":
        th = AdaptiveThreshold(base=DecayingThreshold(0.9, 0.4, 0.5),
                               target_rate=target_rate)
    else:
        th = DecayingThreshold(tau0=1.0, tau_inf=0.45, k=0.8)
    ctrl = AdmissionController(threshold=th)
    ctrl.cost.weights = w
    return ctrl


def _arrivals(args, labels, payloads=None):
    if args.traffic == "bursty":
        return bursty_arrivals(args.requests, args.qps, args.qps * 8,
                               seed=args.seed, payloads=payloads,
                               labels=labels)
    return poisson_arrivals(args.requests, args.qps, seed=args.seed,
                            payloads=payloads, labels=labels)


def serve_classifier(args) -> tuple[dict, list]:
    """One classifier serving run on ``--path``; returns the summary
    and the responses."""
    tracker = Tracker(root=args.runs)
    run = tracker.start_run(f"serve-{args.controller}-{args.path}")
    carbon = CarbonTracker(region=args.region)
    path = canonical_path(args.path)

    cfg, params, data = build_classifier()
    toks, labels, _ = data.sample(args.requests)

    ctrl = make_controller(args.controller, weights=args.weights,
                           target_rate=args.target_rate)

    if path == "gated-in-graph":
        # live in-graph admission over the real model; carbon window
        # wraps the serving run itself.  The open baseline lifts the
        # gate's static capacity to the full batch so it admits 100%
        # like the open baseline on every other path.
        cap = args.max_batch if args.controller == "open" else None
        port = GatedEngineAdapter(cfg, params, batch=args.max_batch,
                                  capacity=cap, exit_layer=1)
        reqs = _arrivals(args, labels, payloads=toks)
    else:
        # precompute the oracle (one vectorised pass — what carbon
        # measures here), calibrate latency models from measured
        # walltimes, then replay through the virtual-time backend
        engine = ClassifierEngine(cfg, params, exit_layer=1)
        carbon.start()
        proxy_pred, entropy, _, t_proxy = engine.proxy_scores(toks)
        full_pred, _ = engine.classify(toks)
        carbon.stop(args.requests)
        times = engine.calibrate(seq_len=toks.shape[1],
                                 buckets=(1, 4, 16))
        t1, t16 = times[1], times[16]
        t_tok = max((t16 - t1) / 15, 1e-5)
        direct_lat = LatencyModel(t_fixed_s=max(t1 - t_tok, 1e-4),
                                  t_tok_s=t_tok)
        batched_lat = LatencyModel(t_fixed_s=max(t1 - t_tok, 1e-4) * 6,
                                   t_tok_s=t_tok)
        oracle = Oracle(full_pred=full_pred, proxy_pred=proxy_pred,
                        entropy=entropy, labels=labels,
                        proxy_latency=LatencyModel(
                            t_proxy / len(toks), 0.0))
        port = OracleEngine(
            oracle, DirectPath(direct_lat),
            DynamicBatcher(batched_lat, max_batch_size=args.max_batch,
                           queue_window_s=args.window))
        reqs = _arrivals(args, labels)

    tracer, metrics, audit = make_observability(args)
    telem = TelemetryMiddleware(run=run)
    server = Server(port, ServerConfig(path=path),
                    middleware=[AdmissionMiddleware(ctrl), telem],
                    tracer=tracer, metrics=metrics)
    if path == "gated-in-graph":
        carbon.start()
        responses = server.serve(reqs)
        carbon.stop(args.requests)
    else:
        responses = server.serve(reqs)
    summary = server.summary()
    summary["controller"] = args.controller
    summary["path"] = path
    drift = finish_observability(args, run, tracer, metrics, audit,
                                 modelled_j=server.energy_j,
                                 n_requests=args.requests)
    if drift:
        summary["energy_drift_ratio"] = drift["drift_ratio"]

    run.log_params(**vars(args))
    run.log_metrics(0, **{k: v for k, v in summary.items()
                          if isinstance(v, (int, float))})
    run.log_artifact("summary.json", summary)
    run.log_artifact("carbon.json", carbon.report())
    run.finish()
    return summary, responses


def serve_fleet(args) -> dict:
    """Run a traffic scenario over a heterogeneous replica fleet —
    oracle-backed virtual-time replicas by default, the LIVE engines
    (real jit'd models, measured walltimes) with ``--fleet-live``."""
    from repro.faults import (BrownoutController, FaultInjector,
                              RetryPolicy, make_chaos)
    from repro.fleet import (Autoscaler, FleetSimulator,
                             LIVE_REPLICA_KINDS, REPLICA_KINDS,
                             build_live_fleet, build_sim_fleet,
                             make_router, make_scenario, with_deadline,
                             with_payloads)

    kinds = tuple(k.strip() for k in args.fleet_kinds.split(","))
    valid = LIVE_REPLICA_KINDS if args.fleet_live else REPLICA_KINDS
    for k in kinds:
        if k not in valid:
            raise SystemExit(f"unknown replica kind {k!r}; choose from "
                             f"{valid}")

    chaos = None
    deadline = args.deadline
    if args.chaos:
        # a named failure story: its traffic trace + fault plan +
        # default deadline, reproducible per --chaos-seed
        chaos = make_chaos(args.chaos, args.requests, qps=args.qps,
                           seed=args.chaos_seed)
        scenario = chaos.scenario
        if deadline is None:
            deadline = chaos.deadline_s
    else:
        scenario = make_scenario(args.scenario, args.requests,
                                 qps=args.qps, seed=args.seed)
    if deadline is not None:
        scenario = with_deadline(scenario, deadline)

    def controllers(kind, i):
        # each replica gets its OWN closed-loop controller
        return make_controller(args.controller, weights=args.weights,
                               target_rate=args.target_rate)

    if args.fleet_live:
        cfg, params, data = build_classifier(seed=args.seed)
        toks, labels, _ = data.sample(args.requests)
        scenario = with_payloads(scenario, toks, labels=labels)
        pool = build_live_fleet(cfg, params, kinds=kinds,
                                controller_factory=controllers,
                                max_batch=args.max_batch,
                                queue_window_s=args.window,
                                seq_len=toks.shape[1])
    else:
        pool = build_sim_fleet(scenario.oracle, kinds=kinds,
                               controller_factory=controllers,
                               max_batch=args.max_batch,
                               queue_window_s=args.window,
                               n_slots=args.slots)
    carbon = CarbonTracker(region=args.region)
    tracer, metrics, audit = make_observability(args)
    sim = FleetSimulator(
        pool, make_router(args.policy),
        autoscaler=Autoscaler() if args.autoscale else None,
        carbon=carbon, tracer=tracer, metrics=metrics,
        injector=(FaultInjector(chaos.plan) if chaos else None),
        retry_policy=(RetryPolicy() if chaos else None),
        brownout=(BrownoutController() if chaos else None))
    report = sim.run(scenario.requests)

    tracker = Tracker(root=args.runs)
    mode = "fleet-live" if args.fleet_live else "fleet"
    tag = f"chaos-{chaos.name}" if chaos else scenario.name
    run = tracker.start_run(f"{mode}-{tag}-{args.policy}")
    drift = finish_observability(
        args, run, tracer, metrics, audit,
        modelled_j=float(report.summary.get("energy_j", 0.0)),
        n_requests=int(report.summary.get("n", args.requests)))
    if drift:
        report.summary["energy_drift_ratio"] = drift["drift_ratio"]
    run.log_params(**{k: str(v) for k, v in vars(args).items()})
    run.log_metrics(0, **{k: v for k, v in report.summary.items()
                          if isinstance(v, (int, float))})
    run.log_artifact("fleet_summary.json", report.summary)
    run.log_artifact("fleet_replicas.json", report.per_replica)
    run.log_artifact("carbon.json", report.carbon)
    if report.autoscaler_log:
        run.log_artifact("autoscaler.json", report.autoscaler_log)
    run.finish()

    out = {"scenario": scenario.name,
           "description": scenario.description,
           "policy": args.policy,
           "live": bool(args.fleet_live),
           "autoscale": bool(args.autoscale),
           **({"chaos": chaos.name,
               "fault_plan": chaos.plan.signature(),
               "deadline_s": deadline} if chaos else {}),
           **report.summary,
           "per_replica": report.per_replica,
           "autoscaler_actions": len(report.autoscaler_log),
           "carbon": report.carbon}
    print(json.dumps(out, indent=2, default=str))
    return out


def serve_disagg(args) -> dict:
    """``--fleet-disagg``: a generate scenario over the disaggregated
    prefill/decode fleet — separate phase pools over one LM weight
    copy, phase-aware routing, an autoscaler per phase."""
    from repro.disagg import (DisaggSimulator, PhaseAwareRouter,
                              build_disagg_fleet)
    from repro.fleet import Autoscaler, make_generate_scenario

    cfg = get_smoke_config(args.arch).replace(
        remat=False, attn_impl=args.attn_impl,
        kv_block_size=args.kv_block_size,
        kv_pool_blocks=args.kv_pool_blocks)
    cfg = _apply_sampling_cfg(cfg, args)
    params = tfm.init_lm(cfg, jax.random.PRNGKey(args.seed))
    scenario = make_generate_scenario(args.scenario, args.requests,
                                      qps=args.qps, seed=args.seed,
                                      vocab=cfg.vocab)
    pool = build_disagg_fleet(cfg, params,
                              n_prefill=args.prefill_workers,
                              n_decode=args.decode_workers,
                              n_slots=args.slots, max_seq=64,
                              draft_depth=args.draft_depth)
    tracer, metrics, audit = make_observability(args)
    sim = DisaggSimulator(
        pool, router=PhaseAwareRouter(),
        prefill_scaler=Autoscaler() if args.autoscale else None,
        decode_scaler=Autoscaler() if args.autoscale else None,
        tracer=tracer, metrics=metrics)
    report = sim.run(scenario.requests)

    tracker = Tracker(root=args.runs)
    run = tracker.start_run(f"fleet-disagg-{scenario.name}")
    drift = finish_observability(
        args, run, tracer, metrics, audit,
        modelled_j=float(report.summary.get("energy_j", 0.0)),
        n_requests=int(report.summary.get("n", args.requests)))
    if drift:
        report.summary["energy_drift_ratio"] = drift["drift_ratio"]
    run.log_params(**{k: str(v) for k, v in vars(args).items()})
    run.log_metrics(0, **{k: v for k, v in report.summary.items()
                          if isinstance(v, (int, float))})
    run.log_artifact("disagg_summary.json", report.summary)
    run.log_artifact("disagg_workers.json", report.per_worker)
    run.finish()

    out = {"scenario": scenario.name,
           "description": scenario.description,
           **report.summary,
           "per_worker": report.per_worker,
           "transfer": report.transfer,
           "autoscaler_actions": {
               k: len(v) for k, v in report.autoscaler_log.items()}}
    print(json.dumps(out, indent=2, default=str))
    return out


def _sampling_cfg_fields(args) -> dict:
    """cfg.replace(...) kwargs for the sampling/speculation flags —
    shared by the pooled and disaggregated generate paths."""
    draft_layers = args.draft_layers
    if args.draft_depth > 0 and draft_layers == 0:
        # auto: the deepest shallow-exit prefix the stack allows
        draft_layers = -1          # resolved per-arch below
    return dict(temperature=args.temperature,
                sample_top_k=args.top_k,
                sample_top_p=args.top_p,
                draft_layers=draft_layers)


def _apply_sampling_cfg(cfg, args):
    fields = _sampling_cfg_fields(args)
    if fields["draft_layers"] == -1:
        fields["draft_layers"] = max(cfg.n_layers - 1, 1)
    return cfg.replace(**fields)


def serve_generate(args, cfg: ModelConfig | None = None, *,
                   max_seq: int = 128,
                   prompt_len: int = 16) -> tuple[dict, list, Server]:
    """Continuous-decode generation of ``--requests`` seeded prompts of
    ``prompt_len`` tokens; returns the summary, the responses and the
    server.  ``cfg`` is the model (default: the smoke config of
    ``--arch``); the attention, KV-pool and sampling flags apply on
    top of it either way."""
    cfg = (cfg or get_smoke_config(args.arch)).replace(
        attn_impl=args.attn_impl,
        kv_block_size=args.kv_block_size,
        kv_pool_blocks=args.kv_pool_blocks)
    cfg = _apply_sampling_cfg(cfg, args)
    params = tfm.init_lm(cfg, jax.random.PRNGKey(args.seed))
    engine = ContinuousBatchingEngine(cfg, params, n_slots=args.slots,
                                     max_seq=max_seq,
                                     draft_depth=args.draft_depth)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab, size=(args.requests, prompt_len))
    prompts = prompts.astype(np.int32)
    ctrl = make_controller(args.controller, weights=args.weights,
                           target_rate=args.target_rate)
    tracer, metrics, audit = make_observability(args)
    port = ContinuousEngineAdapter(engine, prompt_len=prompt_len)
    server = Server(port, ServerConfig(path="continuous-decode"),
                    middleware=[AdmissionMiddleware(ctrl)],
                    tracer=tracer, metrics=metrics)
    reqs = [InferRequest(rid=i, arrival_s=0.001 * i, payload=prompts[i],
                         kind="generate", max_new=args.new_tokens,
                         entropy_hint=float(rng.uniform(0, 1)))
            for i in range(args.requests)]
    responses = server.serve(reqs)
    summary = server.summary()
    drift = finish_observability(args, None, tracer, metrics, audit,
                                 modelled_j=server.energy_j,
                                 n_requests=args.requests)
    if drift:
        summary["energy_drift_ratio"] = drift["drift_ratio"]
    summary.pop("accuracy", None)     # no labels in generation mode
    # decode windows complete mid-stream now, so the LAST response may
    # be a skip — the cumulative session stats ride on the last
    # continuous-path completion
    decode_stats = {}
    for r in reversed(responses):
        if "decode_steps" in r.telemetry:
            decode_stats = {k: r.telemetry[k]
                            for k in ("decode_steps", "occupancy",
                                      "acceptance_rate",
                                      "accepted_per_step",
                                      "energy_per_token_model",
                                      "draft_depth_live")
                            if k in r.telemetry}
            break
    summary.update(
        arch=args.arch, path="continuous-decode",
        controller=args.controller, attn_impl=args.attn_impl,
        kv_block_size=args.kv_block_size,
        temperature=args.temperature, draft_depth=args.draft_depth,
        tokens_generated=sum(len(r.output) for r in responses),
        sample=(responses[0].output[:8] if responses else []),
        **decode_stats)
    print(json.dumps(summary, default=str, indent=2))
    return summary, responses, server


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["classify", "generate"],
                    default="classify")
    ap.add_argument("--arch", choices=list(ARCH_IDS),
                    default="stablelm-3b")
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--qps", type=float, default=None,
                    help="arrival rate (default: 150 single-server, "
                         "40 fleet — small sim fleets saturate at the "
                         "single-server default)")
    ap.add_argument("--traffic", choices=["poisson", "bursty"],
                    default="poisson")
    ap.add_argument("--controller",
                    choices=["open", "bio", "adaptive"], default="bio")
    ap.add_argument("--weights",
                    choices=["balanced", "performance", "ecology"],
                    default="balanced")
    ap.add_argument("--target-rate", type=float, default=0.6)
    ap.add_argument("--path",
                    choices=["direct", "batched", "dynamic-batch",
                             "gated", "gated-in-graph", "auto"],
                    default="auto")
    ap.add_argument("--attn-impl",
                    choices=["auto", "xla", "ref", "pallas"],
                    default="auto",
                    help="attention dispatch for --mode generate: "
                         "'auto' (default) routes attn layers through "
                         "the repro.kernels flash/flash-decode kernels "
                         "— compiled Pallas on TPU, the model's einsum "
                         "path (bitwise = 'xla') elsewhere; 'xla' "
                         "forces the chunked-jnp path everywhere "
                         "(parity oracle)")
    ap.add_argument("--kv-block-size", type=int, default=0,
                    help="generate mode: paged KV pool block size in "
                         "rows (0 = contiguous per-slot cache)")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="generate mode: physical blocks in the paged "
                         "pool (0 = capacity parity with contiguous)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="generate mode: sampling temperature (0 = "
                         "greedy argmax, byte-identical to the default "
                         "path)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="generate mode: keep only the k highest "
                         "logits before sampling (0 = no cap)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="generate mode: nucleus sampling mass "
                         "(1.0 = no cap)")
    ap.add_argument("--draft-depth", type=int, default=0,
                    help="generate mode: self-speculative decode — "
                         "draft up to this many tokens per step with "
                         "the shallow prefix, verify in one chunked "
                         "full pass (0 = off; contiguous KV only)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="layers in the shallow-exit draft prefix "
                         "(0 = auto n_layers-1 when --draft-depth>0)")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--window", type=float, default=0.01)
    ap.add_argument("--region", default="world_avg")
    # observability (repro.telemetry.trace / .metrics / .drift)
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON (load it at "
                         "https://ui.perfetto.dev) covering every "
                         "request's triage/queue/execute spans; "
                         "enables tracing for the run")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics-registry snapshot (JSON) "
                         "plus a Prometheus text sibling (.prom); "
                         "enables metrics for the run")
    ap.add_argument("--energy-source", default="process",
                    choices=["process", "nvml", "tpu"],
                    help="measured-energy reader for the drift audit "
                         "(modelled vs measured joules); the default "
                         "process-time proxy works everywhere")
    ap.add_argument("--runs", default="runs")
    ap.add_argument("--seed", type=int, default=0)
    # fleet mode
    ap.add_argument("--fleet", action="store_true",
                    help="serve through the multi-replica fleet layer")
    ap.add_argument("--fleet-live", action="store_true",
                    help="fleet over the LIVE engine adapters (real "
                         "jit'd models, measured walltimes) instead of "
                         "oracle-backed virtual-time replicas; implies "
                         "--fleet (kinds limited to the classifier "
                         "paths)")
    ap.add_argument("--fleet-disagg", action="store_true",
                    help="generate scenario over the disaggregated "
                         "prefill/decode fleet (separate phase pools, "
                         "phase-aware routing, an autoscaler per "
                         "phase); scenarios limited to the generate "
                         "pair (prompt-burst, long-decode)")
    ap.add_argument("--prefill-workers", type=int, default=2)
    ap.add_argument("--decode-workers", type=int, default=2)
    ap.add_argument("--scenario", default="flash-crowd",
                    choices=["steady", "flash-crowd", "diurnal",
                             "multi-tenant", "low-confidence-flood",
                             "prompt-burst", "long-decode"])
    ap.add_argument("--policy", default="energy-aware",
                    choices=["energy-aware", "round-robin",
                             "least-loaded", "static"])
    ap.add_argument("--fleet-kinds",
                    default="direct,dynamic-batch,gated-in-graph",
                    help="comma-separated replica kinds (>=1)")
    ap.add_argument("--no-autoscale", dest="autoscale",
                    action="store_false", default=True)
    # failure model (repro.faults)
    ap.add_argument("--chaos", default=None,
                    help="named fault-injection story over the fleet "
                         "(crash-storm, slow-node, kv-pressure, "
                         "link-flap, crash-and-flap, seeded-storm): "
                         "scripted/seeded crashes, degradations and "
                         "link outages with bounded retry + brownout; "
                         "implies --fleet")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="seed for the chaos traffic trace and any "
                         "seeded fault schedule (default: --seed)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request completion deadline in seconds; "
                         "queued work past it is shed as a rejection-"
                         "with-reason (default: the chaos scenario's "
                         "deadline, or none)")
    return ap


def main():
    ap = build_parser()
    args = ap.parse_args()
    print(f"compilation cache: {enable_compilation_cache()}")
    if args.chaos:
        args.fleet = True
    if args.chaos_seed is None:
        args.chaos_seed = args.seed
    if args.fleet_live:
        args.fleet = True
    if args.qps is None:
        args.qps = 40.0 if (args.fleet or args.fleet_disagg) else 150.0

    if args.fleet_disagg:
        if args.fleet:
            raise SystemExit("--fleet-disagg and --fleet are separate "
                             "layers; pick one")
        if args.scenario not in ("prompt-burst", "long-decode"):
            if args.scenario == ap.get_default("scenario"):
                args.scenario = "prompt-burst"
            else:
                raise SystemExit(
                    f"--fleet-disagg serves generate traffic; "
                    f"--scenario must be prompt-burst or long-decode, "
                    f"not {args.scenario!r}")
        if args.requests == ap.get_default("requests"):
            args.requests = 48        # generate requests are heavy
        serve_disagg(args)
        return
    if args.fleet:
        # refuse single-server flags that fleet mode would silently
        # ignore (misleading experiment configs otherwise)
        ignored = [f"--{k} {getattr(args, k)}"
                   for k in ("mode", "path", "traffic")
                   if getattr(args, k) != ap.get_default(k)]
        if ignored:
            raise SystemExit(
                f"--fleet does not use {', '.join(ignored)}; fleet "
                f"traffic comes from --scenario and replicas from "
                f"--fleet-kinds")
        serve_fleet(args)
        return
    if args.mode == "generate":
        serve_generate(args)
        return
    summary, _ = serve_classifier(args)
    print(json.dumps(summary, indent=2))


if __name__ == "__main__":
    main()
