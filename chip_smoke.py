"""Smoke run of the two device programs on a TPU, through their entry
points, at the published widths of the models they serve.

  python chip_smoke.py               # one chip: train phase, serve phase
  python chip_smoke.py --four-chips  # four-chip host: MAR across chips

One chip (the default):

* train: ``repro.launch.train.main`` on xlstm-350m (published config),
  2 peers on a (2,) MAR grid, 1 local step, batch 1, seq 2048, 5 steps.
  The loss must be finite and fall from the first step to the last.
* serve: ``repro.launch.serve`` on starcoder2-3b (published config),
  8 sessions with mixed prompt lengths up to 128 tokens, 16 new tokens
  each, 4 decode rows. Every session must drain, the pool must be
  quiescent, the compiled decode step must hold the Pallas paged kernel
  (``tpu_custom_call``), and that kernel must agree with
  ``ref.paged_decode_attention_ref`` on the served KV pages.

``--four-chips`` runs only the cross-chip phase: xlstm-350m with 4 peers,
one per chip, on a (data=4, model=1) mesh and a (2,2) MAR grid. The
aggregated step's params must equal the float64 host mean of the same
step run without aggregation, within bf16 tolerance, and the compiled
program must split the peer axis (about a quarter of the argument bytes
per device, collectives present).

The script exits non-zero, and prints no ``ok`` line, when JAX finds no
TPU or any phase fails. Everything runs in this one process. The last
line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"

TRAIN_ARGV = ["--arch", "xlstm-350m", "--peers", "2", "--local-steps", "1",
              "--batch", "1", "--seq", "2048", "--steps", "5"]
SERVE_ARGV = ["--arch", "starcoder2-3b", "--sessions", "8",
              "--prompt-len", "128", "--vary-prompts", "--gen", "16",
              "--max-batch", "4"]
FOUR_CHIP = {"arch": "xlstm-350m", "smoke": False, "seq": 2048,
             "batch": 1}


def require(cond, msg) -> None:
    """A phase's check; raises (``assert`` would vanish under -O)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def require_tpu():
    """The platform check: runs before anything else touches the repo."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); this script runs on a TPU "
                 f"only")
    return devices


def train_phase(argv, metrics_path) -> dict:
    """Run the trainer's CLI entry point and check its loss curve."""
    from repro.launch import train
    from repro.runtime.metrics import read_metrics

    metrics_path = Path(metrics_path)
    metrics_path.unlink(missing_ok=True)
    rc = train.main(list(argv) + ["--metrics", str(metrics_path)])
    require(rc == 0, f"train.main returned {rc}")
    recs = read_metrics(str(metrics_path))
    losses = [r["loss"] for r in recs]
    step_s = [r["step_s"] for r in recs]
    require(len(losses) >= 2, f"losses {losses}")
    require(all(math.isfinite(x) for x in losses), f"losses {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    steady = sorted(step_s[1:])[len(step_s[1:]) // 2]
    print(f"[smoke] train: first step {step_s[0]:.3f} s (compile + run), "
          f"median later step {steady * 1e3:.3f} ms, losses {losses}")
    return {"losses": losses, "step_s": step_s}


def check_paged_kernel(srv, seed: int = 0) -> float:
    """The paged kernel on the served KV pages (layer 0, the engine's
    batch and table width) against the float32 oracle. Returns the max
    abs error."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.paged_attention import paged_decode_attention_fwd

    kp, vp = srv.pages["k_pages"][0], srv.pages["v_pages"][0]
    nb, kvh, bs, d = kp.shape
    mb, tw = srv.cfg.max_batch, srv.cfg.table_width
    h = srv.model.cfg.num_heads
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(rng.choice(np.arange(1, nb), size=(mb, tw),
                                replace=mb * tw > nb - 1), jnp.int32)
    lens = jnp.asarray(rng.integers(1, tw * bs + 1, mb), jnp.int32)
    q = jnp.asarray(rng.normal(size=(mb, h, d)), kp.dtype)
    out = paged_decode_attention_fwd(
        q, kp, vp, bt, lens, interpret=jax.default_backend() != "tpu")
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_attention_ref(
            q.astype(jnp.float32), kp.astype(jnp.float32),
            vp.astype(jnp.float32), bt, lens)
    out, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(out, want, atol=8e-2, rtol=2e-2)
    return float(np.max(np.abs(out - want)))


def decode_step_hlo(srv) -> str:
    """Compiled HLO text of the engine's jitted paged decode step."""
    import jax.numpy as jnp
    mb, tw = srv.cfg.max_batch, srv.cfg.table_width
    zeros = jnp.zeros((mb,), jnp.int32)
    return srv._decode.lower(
        srv.params, srv.pages, jnp.zeros((mb, tw), jnp.int32), zeros,
        zeros).compile().as_text()


def serve_phase(argv) -> dict:
    """Drain the sessions through the serving entry point; check the
    drain and the paged kernel."""
    from repro.launch import serve

    args = serve.parse_args(argv)
    t0 = time.perf_counter()
    finished, srv = serve.serve(args)
    elapsed = time.perf_counter() - t0
    require(srv is not None, "the paged engine did not run")
    require(len(finished) == args.sessions,
            f"{len(finished)} of {args.sessions} sessions finished")
    require(all(len(s.generated) == args.gen for s in finished),
            "a session stopped short of --gen tokens")
    srv.assert_quiescent()
    tokens = sum(len(s.generated) for s in finished)
    err = check_paged_kernel(srv, seed=args.seed)
    st = srv.stats()
    print(f"[smoke] serve: {len(finished)} sessions, {tokens} tokens in "
          f"{elapsed:.3f} s including compile, {st['decode_steps']} decode "
          f"steps, per-token p50 {st['p50_tok_s'] * 1e3:.3f} ms, "
          f"kernel vs ref max abs err {err:.3e}")
    return {"srv": srv, "tokens": tokens, "kernel_err": err}


def four_chip_phase(arch: str, smoke: bool, seq: int, batch: int,
                    seed: int = 0, lr: float = 0.1) -> dict:
    """4 peers, one per device, MAR grid (2,2) across devices; checks the
    aggregated step against the host mean of the unaggregated one."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.registry import get_config, get_smoke_config
    from repro.core.fl_device import (fl_state_shape, init_fl_state,
                                      make_fl_train_step)
    from repro.core.moshpit import plan_grid
    from repro.data.synthetic import lm_batch
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model
    from repro.runtime.sharding import (batch_shardings, make_shard_plan,
                                        state_shardings)

    n = 4
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = Model(cfg)
    mesh = make_test_mesh((n, 1))
    splan = make_shard_plan(mesh)
    grid = plan_grid(n, group_size=2, depth=2)
    require(splan.n_peers == n and tuple(grid.dims) == (2, 2),
            f"peers {splan.n_peers}, grid {grid.dims}")
    st_sh = state_shardings(fl_state_shape(model, n), splan,
                            head_dim=cfg.head_dim, num_heads=cfg.num_heads,
                            num_kv_heads=cfg.num_kv_heads)
    state = jax.jit(lambda k: init_fl_state(model, n, k),
                    out_shardings=st_sh)(jax.random.PRNGKey(seed))
    raw = lm_batch(cfg.vocab_size, n * batch, seq, seed=seed)
    data = {k: v.reshape(n, 1, 1, batch, seq) for k, v in raw.items()}
    b_sh = batch_shardings(data, splan)
    data = jax.device_put(data, b_sh)
    out_sh = (st_sh, {"loss": NamedSharding(mesh, P())})

    def build(aggregate):
        step = make_fl_train_step(model, grid, lr=lr, aggregate=aggregate)
        t0 = time.perf_counter()
        c = jax.jit(step, in_shardings=(st_sh, b_sh),
                    out_shardings=out_sh).lower(state, data).compile()
        return c, time.perf_counter() - t0

    agg_step, agg_compile_s = build(True)
    local_step, local_compile_s = build(False)

    whole = sum(x.nbytes for x in jax.tree.leaves((state, data)))
    per_dev = agg_step.memory_analysis().argument_size_in_bytes
    share = per_dev / whole
    require(0.2 <= share <= 0.3, f"per-device argument share {share:.3f}")
    hlo = agg_step.as_text()
    collectives = sorted(op for op in ("all-reduce", "all-gather",
                                       "reduce-scatter",
                                       "collective-permute", "all-to-all")
                         if op in hlo)
    require(collectives, "no collective in the aggregated step")

    # per-peer outputs of the unaggregated step -> float64 host mean, and
    # the largest peer magnitude per element. The bound is two bf16 ulps
    # of that magnitude: MAR rounds to bf16 after each of its two
    # rounds, and the two programs may round a local update apart
    local_state, local_metrics = local_step(state, data)
    jax.block_until_ready(local_state)
    host = [np.asarray(x, np.float64)
            for x in jax.tree.leaves(local_state["params"])]
    del local_state
    ref_mean = [x.mean(axis=0) for x in host]
    ref_mag = [np.abs(x).max(axis=0) for x in host]
    del host
    t0 = time.perf_counter()
    agg_state, agg_metrics = agg_step(state, data)
    jax.block_until_ready(agg_state)
    step_s = time.perf_counter() - t0

    worst = 0.0      # max |MAR - host mean| in units of the operand
    for got, want, mag in zip(jax.tree.leaves(agg_state["params"]),
                              ref_mean, ref_mag):
        # every peer holds the global mean after MAR over the full grid
        err = np.abs(np.asarray(got, np.float64) - want[None])
        rel = err / np.maximum(mag[None], 1e-30)
        worst = max(worst, float(rel.max()))
    require(worst <= 2 ** -6,
            f"MAR differs from the host mean by {worst:.3e} of the operand")
    loss = float(agg_metrics["loss"])
    require(math.isfinite(loss) and math.isclose(
        loss, float(local_metrics["loss"]), rel_tol=1e-3),
        f"loss {loss} vs unaggregated {float(local_metrics['loss'])}")
    print(f"[smoke] four-chip: peers={n} grid={grid.dims} "
          f"argument bytes per device {per_dev} of {whole} "
          f"({share:.4f}), collectives {collectives}, compile "
          f"{agg_compile_s:.3f} s (aggregated) {local_compile_s:.3f} s "
          f"(local), aggregated step {step_s * 1e3:.3f} ms incl. dispatch, "
          f"max |MAR - host mean| / max|peer| {worst:.3e} (bound "
          f"{2 ** -6:.3e}), loss {loss:.6f}")
    return {"share": share, "collectives": collectives, "max_rel": worst,
            "loss": loss}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-peer MAR phase across four "
                         "chips")
    args = ap.parse_args(argv)

    devices = require_tpu()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    dev = devices[0]
    print(f"[smoke] device: {dev.platform} {dev.device_kind} "
          f"x{len(devices)}; compile cache {cache}")

    if args.four_chips:
        if len(devices) < 4:
            sys.exit(f"chip_smoke: --four-chips needs 4 devices, JAX "
                     f"found {len(devices)}")
        four_chip_phase(**FOUR_CHIP)
        used = 4
    else:
        train_phase(TRAIN_ARGV, OUT_DIR / "train_metrics.jsonl")
        gc.collect()         # the train state leaves the chip first
        srv = serve_phase(SERVE_ARGV)["srv"]
        require("tpu_custom_call" in decode_step_hlo(srv),
                "the compiled paged decode step holds no Pallas kernel")
        print("[smoke] serve: compiled decode step holds tpu_custom_call")
        used = 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": used}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
