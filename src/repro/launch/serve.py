"""Serve launcher: prefill + decode loop for any assigned arch, or — with
``--sched-status`` — a fleet-status HTTP endpoint exposing scheduler
telemetry (Prometheus ``/metrics``, Perfetto ``/trace.json``, ``/healthz``)
for a simulated schedule (the ROADMAP's fleet-status service substrate).

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --batch 4 --prompt-len 16 --gen 24
  PYTHONPATH=src python -m repro.launch.serve --sched-status --port 9090 \
      --policy omfs --tenants 4 --chips 64 --horizon 300
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models.model import build_model
from repro.launch.compile_cache import enable_compile_cache


def sched_status_payloads(args):
    """Run the configured simulation once and materialize every endpoint's
    response body: ``{path: (content_type, bytes)}``.  Split out from the
    HTTP plumbing so tests can hit the payloads without a socket — and the
    server can serve heavy read traffic from memory without re-simulating
    per scrape."""
    from repro.core import engine
    from repro.core.metrics import event_summary
    from repro.core.types import SchedulerConfig
    from repro.core.workload import WorkloadSpec, make_jobs, make_users
    from repro.obs import registry_from_result, trace_from_result

    spec = WorkloadSpec(n_users=args.tenants, horizon=args.horizon,
                        cpu_total=args.chips, seed=args.seed,
                        arrival_rate=args.arrival_rate)
    users = make_users(spec)
    jobs = make_jobs(spec, users)
    cfg = SchedulerConfig(cpu_total=args.chips, quantum=args.quantum,
                          cr_overhead=2)
    res = engine.simulate(users, jobs, cfg, args.horizon, policy=args.policy,
                          backend=args.backend, record_events=True)
    reg = registry_from_result(res, users=users)
    trace = trace_from_result(res, users=users)
    health = {"status": "ok", "policy": args.policy, "backend": args.backend,
              "horizon": args.horizon, "events": len(res.events),
              "events_dropped": res.events_dropped_total(),
              "summary": event_summary(res.events)}
    return {
        "/metrics": ("text/plain; version=0.0.4",
                     reg.to_prometheus().encode()),
        "/trace.json": ("application/json", json.dumps(trace).encode()),
        "/healthz": ("application/json", json.dumps(health).encode()),
    }


def serve_sched_status(args):
    """Serve the scheduler-status payloads over stdlib HTTP."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    payloads = sched_status_payloads(args)

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            hit = payloads.get(self.path.split("?", 1)[0])
            if hit is None:
                self.send_error(404, explain=f"known: {sorted(payloads)}")
                return
            ctype, body = hit
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, fmt, *a):   # quiet scrape spam
            pass

    server = ThreadingHTTPServer((args.host, args.port), Handler)
    host, port = server.server_address[:2]
    print(f"sched-status on http://{host}:{port}  "
          f"endpoints: {' '.join(sorted(payloads))}")
    try:
        if args.max_requests > 0:
            for _ in range(args.max_requests):
                server.handle_request()
        else:
            server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
    return 0


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    # -- scheduler fleet-status mode (repro.obs telemetry over HTTP) -------
    ap.add_argument("--sched-status", action="store_true",
                    help="serve scheduler telemetry for a simulated fleet "
                         "instead of running a model")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=9090)
    ap.add_argument("--policy", default="omfs")
    ap.add_argument("--backend", default="jax", choices=["python", "jax"])
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--chips", type=int, default=64)
    ap.add_argument("--horizon", type=int, default=300)
    ap.add_argument("--quantum", type=int, default=10)
    ap.add_argument("--arrival-rate", type=float, default=0.08)
    ap.add_argument("--max-requests", type=int, default=0,
                    help="serve N requests then exit (0 = forever); "
                         "lets smoke tests and CI probes terminate")
    args = ap.parse_args(argv)

    if args.sched_status:
        return serve_sched_status(args)
    if args.arch is None:
        ap.error("--arch is required unless --sched-status is given")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg, q_chunk=64, kv_chunk=64)
    params = model.init(jax.random.PRNGKey(args.seed))
    key = jax.random.PRNGKey(args.seed + 1)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["frontend"] = jnp.zeros(
            (args.batch, cfg.vision.n_patches, cfg.vision.vision_dim), jnp.bfloat16)
    if cfg.family == "audio":
        batch["frontend"] = jnp.zeros(
            (args.batch, cfg.audio.n_audio_ctx, cfg.d_model), jnp.bfloat16)

    cache = model.init_cache(args.batch, args.prompt_len + args.gen)
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)

    t0 = time.perf_counter()
    cache, logits = prefill(params, batch, cache)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for _ in range(args.gen - 1):
        cache, logits = decode(params, cache, tok)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} gen={args.gen}")
    print(f"prefill: {t_prefill*1e3:.1f} ms "
          f"({args.batch*args.prompt_len/t_prefill:.0f} tok/s)")
    print(f"decode : {t_decode*1e3:.1f} ms "
          f"({args.batch*(args.gen-1)/max(t_decode,1e-9):.0f} tok/s)")
    print("sample generation row 0:", gen[0].tolist())


if __name__ == "__main__":
    main()
