"""Production serving launcher: one front door for both serving paths.

Default mode — batched greedy LM decode with a preallocated cache (the
dry-run's decode_32k/long_500k step, driven end-to-end)::

    python -m repro.launch.serve --arch gemma3-1b --smoke --new-tokens 16

``--stencil`` mode — the hardened ROI-query service over a curve-ordered
stencil block store (serve/service.py, DESIGN.md §11), mirroring
``launch/elastic.py --stencil``: advance a ResidentPipeline a few steps,
snapshot its block store, and drive a batched ROI query demo through the
full fault matrix (slow/failed fetch, bit-flipped payloads, cache
poison, deadline pressure, admission control), printing a per-request
deadline/outcome summary::

    python -m repro.launch.serve --stencil --M 32 --ordering hilbert \
        --queries 12 --deadline-ms 50 --faults
"""

from __future__ import annotations

import argparse
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="LM mode: model config")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--smoke", action="store_true")
    # stencil ROI-service mode
    ap.add_argument("--stencil", action="store_true",
                    help="serve ROI queries over a stencil block store "
                         "instead of LM decode")
    ap.add_argument("--M", type=int, default=32)
    ap.add_argument("--T", type=int, default=8)
    ap.add_argument("--ordering", default="hilbert")
    ap.add_argument("--rule", default="gol")
    ap.add_argument("--bc", default="periodic")
    ap.add_argument("--steps", type=int, default=4,
                    help="pipeline steps before the snapshot is served")
    ap.add_argument("--queries", type=int, default=12)
    ap.add_argument("--deadline-ms", type=float, default=100.0)
    ap.add_argument("--cache-blocks", type=int, default=256)
    ap.add_argument("--max-in-flight", type=int, default=4)
    ap.add_argument("--faults", action="store_true",
                    help="inject the serving fault matrix (failed + "
                         "bit-flipped fetches, cache poison)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def lm_main(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, get_smoke
    from repro.models import build_model
    from repro.serve import greedy_decode

    if args.arch is None:
        raise SystemExit("LM mode needs --arch (or pass --stencil)")
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family in ("encdec", "vlm"):
        raise SystemExit("frontend-stubbed archs: see examples/serve_lm.py")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab,
                                       (args.batch, args.prompt_len), np.int32))
    t0 = time.perf_counter()
    out = greedy_decode(model, params, prompts, args.new_tokens,
                        args.prompt_len + args.new_tokens + 1)
    out = jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    n = args.batch * args.new_tokens
    print(f"[serve] {cfg.name}: {n} tokens in {dt:.2f}s ({n/dt:.1f} tok/s)")


def _demo_rois(M: int, T: int, n: int, seed: int):
    """Deterministic ROI mix: aligned power-of-two boxes (the
    best-case contiguity suite) plus arbitrary unaligned boxes."""
    import numpy as np

    from repro.serve import ROI

    rois = [ROI((0, 0, 0), (M // 2,) * 3),
            ROI((M // 2,) * 3, (M,) * 3),
            ROI((0, 0, 0), (M, M // 2, M // 2))]
    rng = np.random.default_rng(seed)
    while len(rois) < n:
        lo = rng.integers(0, M - T, 3)
        ext = rng.integers(T, M // 2 + 1, 3)
        hi = np.minimum(lo + ext, M)
        rois.append(ROI(tuple(int(v) for v in lo),
                        tuple(int(v) for v in hi)))
    return rois[:n]


def stencil_main(args) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.faults import ServeFaultPlan, initial_state
    from repro.serve import StencilQueryService, StoreLayout
    from repro.stencil import ResidentPipeline

    pipe = ResidentPipeline(M=args.M, T=args.T, rule=args.rule, bc=args.bc,
                            kind=args.ordering)
    state0 = initial_state(args.rule, args.M, seed=args.seed)
    cube = pipe.run(jnp.asarray(state0), args.steps)
    store = np.asarray(pipe.to_blocks(cube))
    layout = StoreLayout.from_pipeline(pipe)
    print(f"[serve] stencil snapshot: rule={args.rule} M={args.M} "
          f"T={args.T} ordering={args.ordering} C={layout.channels} "
          f"({layout.nb} blocks) after {args.steps} steps")

    svc = StencilQueryService(
        store=store, layout=layout, cache_blocks=args.cache_blocks,
        deadline_s=args.deadline_ms / 1e3, max_in_flight=args.max_in_flight)
    if args.faults:
        plan = ServeFaultPlan(fail_first=2, bitflip_first=1)
        svc.fetch = plan.wrap_fetch(svc.fetch)
        print("[serve] fault injection ON: first 2 fetches fail, "
              "next payload bit-flipped")

    rois = _demo_rois(args.M, args.T, args.queries, args.seed)
    t0 = time.perf_counter()
    results = svc.query_batch(rois)
    dt = time.perf_counter() - t0

    dense = np.asarray(cube)
    for i, (roi, r) in enumerate(zip(rois, results)):
        line = (f"[serve]  q{i:02d} {roi.lo}->{roi.hi} "
                f"status={r.status:9s} ranges={len(r.ranges):2d} "
                f"hits={r.cache_hits:3d} misses={r.cache_misses:3d} "
                f"retries={r.retries} deadline={r.elapsed_s * 1e3:6.1f}ms")
        if r.status in ("ok", "degraded") and r.payload is not None:
            sl = tuple(slice(l, h) for l, h in zip(roi.lo, roi.hi))
            want = dense[(Ellipsis,) + sl]
            served = ~np.isnan(r.payload) if r.status == "degraded" \
                else np.ones_like(r.payload, bool)
            exact = bool(np.array_equal(np.asarray(r.payload)[served],
                                        np.asarray(want)[served]))
            line += f" exact={exact} missing={list(r.missing_ranges)}"
            if not exact:
                raise SystemExit(f"payload mismatch on q{i}")
        print(line)

    by = {}
    for r in results:
        by[r.status] = by.get(r.status, 0) + 1
    s = svc.stats()
    print(f"[serve] {len(results)} queries in {dt * 1e3:.1f}ms: "
          + " ".join(f"{k}={v}" for k, v in sorted(by.items())))
    print(f"[serve] cache: {s['cache_hits']} hits / {s['cache_misses']} "
          f"misses ({s['cached_blocks']} resident), "
          f"fetches={s['fetch_calls']} retries={s['retries']} "
          f"integrity_failures={s['integrity_failures']} "
          f"quarantined={s['quarantined']} shed={s['shed']}")
    print("SERVE_DONE")


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = build_parser().parse_args()
    if args.stencil:
        stencil_main(args)
    else:
        lm_main(args)


if __name__ == "__main__":
    main()
