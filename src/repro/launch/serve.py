"""Decode-serving driver over the continuous-batching engine.

Thin CLI around :mod:`repro.serve`: enqueue N synthetic sessions
(mixed prompt lengths with ``--vary-prompts``), drain them through the
paged-KV :class:`~repro.serve.engine.DecodeServer`, print throughput
and latency percentiles. ``--sequential`` runs the one-session-at-a-time
baseline instead (also the only path for recurrent families, whose
state cannot be paged). ``--ckpt-dir`` serves weights from a training
checkpoint directory and hot-swaps newer checkpoints mid-run;
``--swap-demo`` performs an identity hot-swap mid-drain to demonstrate
zero-drop swapping.

  PYTHONPATH=src python -m repro.launch.serve --smoke --sessions 8 \
      --prompt-len 24 --gen 16 --max-batch 4
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional, Tuple

import jax
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import Model
from repro.serve import (DecodeServer, ServeConfig, Session,
                         run_sequential, serving_params_from_checkpoint)

PAGED = ("dense", "vlm", "audio", "moe")


def _summarize(tag, sessions, elapsed):
    toks = sum(len(s.generated) for s in sessions)
    times = [t for s in sessions for t in s.token_times[1:]]
    p50 = np.percentile(times, 50) * 1e3 if times else 0.0
    p99 = np.percentile(times, 99) * 1e3 if times else 0.0
    print(f"[serve] {tag}: {len(sessions)} sessions, {toks} tokens in "
          f"{elapsed:.2f}s ({toks / max(elapsed, 1e-9):.1f} tok/s), "
          f"per-token p50 {p50:.1f}ms p99 {p99:.1f}ms")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="starcoder2-3b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--vary-prompts", action="store_true",
                    help="mixed prompt lengths in [1, prompt_len]")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int,
                    default=ServeConfig.block_size)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size (default: a full batch's worst case)")
    ap.add_argument("--sequential", action="store_true",
                    help="one-session-at-a-time dense baseline")
    ap.add_argument("--ckpt-dir", default=None,
                    help="serve (and hot-swap) weights from this "
                         "checkpoint directory")
    ap.add_argument("--swap-demo", action="store_true",
                    help="identity hot-swap mid-drain (zero-drop demo)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def serve(args: argparse.Namespace
          ) -> Tuple[List[Session], Optional[DecodeServer]]:
    """Drain ``args.sessions`` synthetic sessions; returns the finished
    sessions and the drained engine (None on the sequential path)."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    rng = np.random.default_rng(args.seed)
    # jitted so each leaf is drawn straight into its dtype: eager init
    # holds a float32 copy of the largest stacked leaf on the device
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))

    ckpt = None
    if args.ckpt_dir:
        from repro.checkpoint.checkpointer import Checkpointer
        ckpt = Checkpointer(args.ckpt_dir)
        if ckpt.latest_step() is not None:
            state, meta = ckpt.restore()
            params = serving_params_from_checkpoint(state, params)
            print(f"[serve] restored step {ckpt.latest_step()} "
                  f"from {args.ckpt_dir} (meta: {meta})")

    paged = cfg.family in PAGED and cfg.frontend == "none" \
        and not args.sequential
    if not paged and (args.vary_prompts and cfg.family not in PAGED):
        print("[serve] recurrent family: fixed-length prompts only")
        args.vary_prompts = False
    plens = (rng.integers(1, args.prompt_len + 1, args.sessions)
             if args.vary_prompts
             else np.full(args.sessions, args.prompt_len))
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in plens]

    if not paged:
        print(f"[serve] sequential baseline ({cfg.family})")
        t0 = time.perf_counter()
        done = run_sequential(model, params, prompts, max_new=args.gen,
                              pad_len=args.prompt_len)
        _summarize("sequential", done, time.perf_counter() - t0)
        print("[serve] sample:", done[0].generated[:16])
        return done, None

    need = -(-(args.prompt_len + args.gen) // args.block_size)
    num_blocks = args.num_blocks or 1 + need * args.max_batch
    scfg = ServeConfig(max_batch=args.max_batch, block_size=args.block_size,
                       num_blocks=num_blocks, pad_len=args.prompt_len,
                       max_new=args.gen)
    srv = DecodeServer(model, params, scfg)
    if ckpt is not None:
        srv.attach_checkpointer(ckpt, params)
    for p in prompts:
        srv.enqueue(p)
    print(f"[serve] engine: {args.sessions} sessions, pool "
          f"{num_blocks}x{args.block_size} KV slots, batch {args.max_batch}")
    t0 = time.perf_counter()
    if args.swap_demo:
        for _ in range(3):
            srv.step()
        srv.swap_params(srv.params, tag="demo-identity")
    srv.run()
    elapsed = time.perf_counter() - t0
    srv.assert_quiescent()
    _summarize("continuous", srv.finished, elapsed)
    st = srv.stats()
    print(f"[serve] {st['prefills']} prefills, {st['decode_steps']} decode "
          f"steps, {st['swaps']} hot-swaps")
    if srv.swap_log:
        print("[serve] swap log:", srv.swap_log)
    print("[serve] sample:", srv.finished[0].generated[:16])
    return srv.finished, srv


def main(argv=None) -> int:
    use_compile_cache()
    serve(parse_args(argv))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
