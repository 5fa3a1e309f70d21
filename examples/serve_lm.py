"""Serve a small model with batched requests: slot-based continuous
batching, prefill + batched decode, per-request latency stats.

With ``--pim-offload`` the decode path is mirrored onto a resident-weight
PIM runtime (weights placed once, balanced placement): each step's
matmuls are accounted on a 16-pseudo-channel stack and the run ends with
the steady-state PIM-vs-host roofline — weights amortized, h2d traffic
is activations only.

With ``--pim-numeric`` the sidecar also *executes* each step's matmul
set on the per-channel engines (weights materialized and resident) and
cross-checks every output — lm_head logits included — against an XLA
reference within FP16 accumulation tolerance.

With ``--profile out.json`` the offload runtime runs in async timeline
mode and the run additionally writes a Chrome-trace profile of the PIM
schedule (open at https://ui.perfetto.dev), prints the critical-path
attribution of the PIM makespan, and reports per-request TTFT/TPOT
percentiles from the serve loop's metrics — see docs/observability.md.

Request timestamps are stamped from a deterministic virtual clock by
default (latency percentiles are simulated seconds, identical across
runs and machines — see docs/serving.md); ``--wall`` restores
``time.time()`` stamping.  ``--traffic RATE`` additionally replays a
seeded Poisson arrival trace through the virtual-time ``TrafficServer``
and prints disaggregated-vs-colocated goodput at an SLO.

  PYTHONPATH=src python examples/serve_lm.py [--requests 12] [--slots 4]
  PYTHONPATH=src python examples/serve_lm.py --pim-offload
  PYTHONPATH=src python examples/serve_lm.py --pim-offload --pim-numeric
  PYTHONPATH=src python examples/serve_lm.py --profile pim_profile.json
  PYTHONPATH=src python examples/serve_lm.py --traffic 50
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as lm
from repro.serve.loop import Request, Server
from repro.serve.offload import DecodeOffload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--pim-offload", action="store_true",
                    help="account decode matmuls on a resident-weight "
                         "PIM runtime and report the roofline")
    ap.add_argument("--pim-channels", type=int, default=16)
    ap.add_argument("--pim-numeric", action="store_true",
                    help="run the offloaded matmuls numerically on the "
                         "per-channel engines, cross-checked against XLA")
    ap.add_argument("--profile", metavar="OUT_JSON", default=None,
                    help="write a Chrome-trace profile of the PIM decode "
                         "schedule here (implies --pim-offload in async "
                         "timeline mode) and report critical-path + "
                         "TTFT/TPOT latency metrics")
    ap.add_argument("--wall", action="store_true",
                    help="stamp request timestamps from time.time() "
                         "instead of the deterministic virtual clock")
    ap.add_argument("--traffic", type=float, metavar="RATE_RPS",
                    default=None,
                    help="also replay a seeded Poisson trace at RATE_RPS "
                         "through the virtual-time TrafficServer and "
                         "print disaggregated vs colocated goodput")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get("qwen3-1.7b").reduced().replace(n_layers=4, d_model=256,
                                              d_ff=512, vocab_size=1024)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    metrics = None
    if args.profile:
        from repro.obs import MetricsRegistry
        metrics = MetricsRegistry()
    offload = DecodeOffload(cfg, channels=args.pim_channels,
                            numeric=args.pim_numeric,
                            async_mode=args.profile is not None,
                            metrics=metrics) \
        if args.pim_offload or args.pim_numeric or args.profile else None
    srv = Server(cfg, params, slots=args.slots, cache_len=160,
                 pim_offload=offload, metrics=metrics, wall=args.wall)

    rng = np.random.default_rng(0)
    t0 = time.time()
    for uid in range(args.requests):
        plen = int(rng.integers(4, 32))
        srv.submit(Request(uid=uid,
                           prompt=rng.integers(0, 1023, plen).astype(np.int32),
                           max_new=args.max_new))
    done = srv.run_until_drained()
    wall = time.time() - t0

    toks = sum(len(r.out_tokens) for r in done)
    lat = [r.finished_at - r.submitted_at for r in done]
    dev = jax.devices()[0]
    print(f"served {len(done)} requests / {toks} tokens in {wall:.2f}s "
          f"({toks / wall:.1f} tok/s on {dev.platform}:{dev.device_kind} "
          f"x{len(jax.devices())}, compiles included, slots={args.slots})")
    unit = "wall" if args.wall else "virtual"
    print(f"latency ({unit} seconds) p50={np.percentile(lat, 50):.4f}s "
          f"p99={np.percentile(lat, 99):.4f}s")
    assert len(done) == args.requests
    if offload is not None:
        roof = offload.roofline()
        print(f"pim offload [{roof['channels']}ch, {roof['placement']}]: "
              f"{len(offload.steps)} decode steps, "
              f"weights={roof['weight_bytes']}B uploaded once "
              f"({roof['upload_bytes']}B sharded)")
        print(f"  steady state (full batch): "
              f"h2d={roof['steady_h2d_bytes']}B (activations only), "
              f"d2h={roof['steady_d2h_bytes']}B, "
              f"weight reuse={roof['steady_reuse_bytes']}B/step")
        if args.pim_numeric:
            err = max(s.numeric_max_err for s in offload.steps)
            lerr = max(s.logits_max_err for s in offload.steps)
            print(f"  numeric decode-on-PIM: every matmul executed on the "
                  f"engines and matched XLA (max err={err:.1e}, "
                  f"lm_head logits err={lerr:.1e})")
        print(f"  roofline: pim={roof['steady_pim_s']:.2e}s vs "
              f"host={roof['steady_host_s']:.2e}s "
              f"({roof['steady_host_bound']}-bound host), "
              f"pim_vs_host={roof['steady_pim_vs_host']:.3f}")
        assert roof["steady_reuse_bytes"] == offload.weight_bytes
    if args.profile:
        from repro.obs import export_chrome_trace, profile_report
        trace = export_chrome_trace(offload.rt, args.profile)
        rep = profile_report(offload.rt)
        print(f"profile: {len(trace['traceEvents'])} events -> "
              f"{args.profile} (open at https://ui.perfetto.dev)")
        print(rep.summary(top_k=5))
        lat_sum = srv.latency_summary()
        ttft, tpot = lat_sum["ttft_s"], lat_sum["tpot_s"]
        print(f"serve latency [{lat_sum['requests']} requests, "
              f"{lat_sum['tokens']} tokens]: "
              f"ttft p50={ttft['p50']:.3f}s p99={ttft['p99']:.3f}s | "
              f"tpot p50={tpot['p50']:.4f}s p99={tpot['p99']:.4f}s")
    if args.traffic:
        from repro.serve.loop import TrafficServer
        from repro.serve.traffic import SLO, HostCostModel, poisson_trace
        off = DecodeOffload(cfg, channels=args.pim_channels)
        cost = HostCostModel(cfg)
        step_s = off.step(args.slots).pim_s
        slo = SLO(ttft_s=4 * cost.prefill_s(256), tpot_s=1.3 * step_s)
        tr = poisson_trace(args.traffic, 200, seed=7, prompt_len=256,
                           max_new=args.max_new)
        print(f"traffic: 200 Poisson arrivals @ {args.traffic:.1f} rps, "
              f"slo(ttft={slo.ttft_s:.4f}s tpot={slo.tpot_s:.5f}s)")
        for label, dis in (("disaggregated", True), ("colocated", False)):
            ts = TrafficServer(off, slots=args.slots, disaggregate=dis,
                               chunk_tokens=64, slo=slo)
            ts.run(tr)
            s = ts.latency_summary()
            print(f"  {label:13s}: goodput={s['goodput_rps']:8.2f} rps  "
                  f"attainment={s['slo_attainment']:.2f}  "
                  f"ttft_p99={s['ttft_s']['p99']:.4f}s  "
                  f"tpot_p99={s['tpot_s']['p99']:.5f}s")
    print("serve_lm OK")


if __name__ == "__main__":
    main()
