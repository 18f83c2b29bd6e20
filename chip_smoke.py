#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              one chip: trainer (ResNet-50 b128, GPT-2
                                      small b8 s1024 with the compiled Pallas
                                      flash kernel) and server (ServeJob, bf16,
                                      paged KV)
    python chip_smoke.py --chips 4    four chips, one process: the gpt-small
                                      data-parallel step against the same
                                      batch on one of those chips, plus one
                                      hierarchical allreduce — nothing else
    python chip_smoke.py --rehearse-cpu [--chips 4]
                                      the same control flow at a tiny size on
                                      CPU devices (never a chip result)

Every phase goes through the entry points a user calls (``hvd.init``,
``horovod_tpu.testing.steps.build_step`` / ``build_gpt_step``, ``ServeJob``
+ ``ServeClient``)
and checks its own output.  A chip belongs to one process at a time, so
this parent never imports JAX: it runs the phases as children, one after
another, and builds its last line from what they reported.  That line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only if every phase passed on platform ``tpu``.  The
seconds printed per phase are smoke observations of one run, not
benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "CHIP_SMOKE_PHASE_RESULT "
# The whole script must end inside the driver's 1200 s, compilation
# included; a phase gets whatever of this is left when it starts.
TOTAL_BUDGET_SECS = 1140.0

ONE_CHIP_PHASES = ("resnet50", "gpt_small", "serve")
FOUR_CHIP_PHASES = ("dp4",)

# bf16 tolerances on a loss of about ln(32000) = 10.4, stated up front.
# Flash vs reference attention: same weights, same batch, same optimizer;
# only the attention schedule's rounding differs, then feeds the AdamW
# steps.  Four chips vs one: same global batch, gradients averaged over
# four shards instead of one reduction.
FLASH_VS_REFERENCE_ATOL = 5e-2
FOUR_VS_ONE_CHIP_ATOL = 5e-2
TRAIN_STEPS = 4


# --------------------------------------------------------------- sizes

def _sizes(rehearse: bool) -> dict:
    """Real sizes, or the tiny ones of the CPU rehearsal."""
    if rehearse:
        return {
            "resnet": ("resnet18", 2, 32),
            "gpt": ("nano", 2, 128),
            "gpt_dp_batch": 1,
            "serve_size": "nano",
            "serve_slots": 2,
            "serve_prompts": [3, 9, 20],
            "serve_budgets": [4, 6, 5],
            "oracle_prompt": 5,
            "oracle_steps": 6,
        }
    return {
        # the source paper's headline model at its real size
        "resnet": ("resnet50", 128, 224),
        # GPT-2 small at its published width: 12 layers x 768, 12 heads
        "gpt": ("small", 8, 1024),
        "gpt_dp_batch": 2,  # per chip; global 8 on four
        "serve_size": "small",
        "serve_slots": 8,
        "serve_prompts": [6, 21, 45, 100, 120, 30],
        "serve_budgets": [16, 24, 32, 12, 20, 16],
        "oracle_prompt": 12,
        "oracle_steps": 16,
    }


# -------------------------------------------------------- phase helpers

def _device_report() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _start_backend(rehearse: bool) -> dict:
    """Initialise JAX in this phase's own process and refuse, before
    anything is built, a platform that is not the chip."""
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = _device_report()
    print(f"# device {device}  compile cache {cache_dir}", flush=True)
    if not rehearse and device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found platform {device['platform']!r}, not "
            "'tpu' — this script needs the chip"
        )
    if device["platform"] == "tpu":
        from horovod_tpu.obs.profile import peak_flops

        # Raises, naming the kind, if the table does not know this chip.
        peak_flops(device["kind"])
    return device


def _run_steps(compiled, state, carry_len: int, steps: int):
    """``steps`` calls of the compiled step, ``float(loss)`` forced each
    time.  Returns (final carry, losses, seconds per step)."""
    carry, const = list(state[:carry_len]), state[carry_len:]
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        *carry, loss = compiled(*carry, *const)
        losses.append(float(loss))
        secs.append(round(time.perf_counter() - t0, 4))
    return carry, losses, secs


def _check_losses(name: str, losses) -> None:
    import math

    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{name}: non-finite loss in {losses}")
    if losses[-1] == losses[0]:
        raise AssertionError(f"{name}: loss never changed: {losses}")


def _compile(step, state):
    t0 = time.perf_counter()
    compiled = step.lower(*state).compile()
    return compiled, round(time.perf_counter() - t0, 2)


# --------------------------------------------------------------- phases

def phase_resnet50(rehearse: bool) -> dict:
    """Trainer, conv: hvd.init -> broadcast_parameters ->
    DistributedOptimizer -> the shard_map+jit step ``testing/steps.py``
    builds."""
    device = _start_backend(rehearse)
    from horovod_tpu.testing.steps import build_step

    model, batch, image = _sizes(rehearse)["resnet"]
    step, state, static = build_step(model, "bf16", batch, image)
    compiled, compile_secs = _compile(step, state)
    _, losses, secs = _run_steps(compiled, state, static["carry_len"],
                                 TRAIN_STEPS)
    _check_losses(model, losses)
    return {"device": device, "model": model, "batch": batch,
            "image_size": image, "compile_secs": compile_secs,
            "step_secs": secs, "losses": losses}


def phase_gpt_small(rehearse: bool) -> dict:
    """Trainer, transformer: the flash step (Pallas kernel compiled, not
    interpreted — proved from the compiled text) against the same step
    built with attention="reference" on the same seed."""
    device = _start_backend(rehearse)
    from horovod_tpu.testing.steps import build_gpt_step

    size, batch, seq = _sizes(rehearse)["gpt"]
    out = {"device": device, "model": f"gpt-{size}", "batch": batch,
           "seq_len": seq}
    for attention in ("flash", "reference"):
        step, state, static = build_gpt_step(
            size, "bf16", batch, seq, attention=attention)
        compiled, compile_secs = _compile(step, state)
        if attention == "flash":
            kernel = "tpu_custom_call" in compiled.as_text()
            out["tpu_custom_call"] = kernel
            if not kernel and not rehearse:
                raise AssertionError(
                    "gpt flash step: no tpu_custom_call in the compiled "
                    "text — the Pallas kernel was not compiled for the chip"
                )
        _, losses, secs = _run_steps(compiled, state, static["carry_len"],
                                     TRAIN_STEPS)
        _check_losses(f"gpt-{size}/{attention}", losses)
        out[attention] = {"compile_secs": compile_secs, "step_secs": secs,
                          "losses": losses}
        del step, state, compiled
    diff = max(abs(a - b) for a, b in zip(out["flash"]["losses"],
                                          out["reference"]["losses"]))
    out["flash_vs_reference_max_abs_diff"] = diff
    out["flash_vs_reference_atol"] = FLASH_VS_REFERENCE_ATOL
    if not diff <= FLASH_VS_REFERENCE_ATOL:
        raise AssertionError(
            f"flash vs reference losses differ by {diff} "
            f"(> {FLASH_VS_REFERENCE_ATOL}): {out['flash']['losses']} vs "
            f"{out['reference']['losses']}"
        )
    return out


def _serve_round(spec: dict, env: dict, prompts, budgets, timeout: float):
    """One ServeJob, np=1, no respawns: submit, collect, drain.  Returns
    (token lists, the rank's drain summary, seconds).  ``env`` is what
    the rank gets on top of this process's environment.  This process
    stays off the backend while the job's rank holds the chip."""
    from horovod_tpu.serve import ServeJob

    t0 = time.perf_counter()
    job = ServeJob(spec, np=1, env=env, max_retries=0,
                   timeout=timeout).start()
    try:
        rids = [job.client.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, budgets)]
        docs = [job.client.result(r, timeout=timeout) for r in rids]
        results, _ = job.stop(timeout=timeout)
    finally:
        job.shutdown()
    if sorted(results) != [0]:
        raise AssertionError(f"serve: expected one rank, got {results}")
    tokens = [list(d["tokens"]) for d in docs]
    for toks, n in zip(tokens, budgets):
        if len(toks) != n:
            raise AssertionError(
                f"serve: asked for {n} tokens, got {len(toks)}")
    return tokens, results[0], round(time.perf_counter() - t0, 2)


def phase_serve(rehearse: bool, timeout: float) -> dict:
    """Server: ServeJob + ServeClient, bf16, paged KV, mixed-length
    requests; then greedy tokens for one prompt against
    models/decode.py:generate on the same seeded weights.

    The bf16 slot pool and single-stream generate are known not to be
    bitwise equal, so that ONE comparison pins dtype=float32 (and, on
    the chip, full-precision matmuls on both sides); the mixed-length
    round above it is bf16."""
    import numpy as np

    sizes = _sizes(rehearse)
    seed = 0
    rs = np.random.RandomState(seed)
    vocab = 1024 if rehearse else 32000
    prompts = [rs.randint(0, vocab, n).tolist()
               for n in sizes["serve_prompts"]]
    spec = {"size": sizes["serve_size"], "seed": seed,
            "num_slots": sizes["serve_slots"], "kv_mode": "paged",
            "page_size": 16, "idle_secs": 0.005}
    # bfloat16 is the model family's default dtype: no override needed.
    tokens, summary, secs = _serve_round(
        spec, None, prompts, sizes["serve_budgets"], timeout)
    rank_device = summary["device"]
    if not rehearse and rank_device["platform"] != "tpu":
        raise AssertionError(f"serve rank ran on {rank_device}")
    if any(not 0 <= t < vocab for toks in tokens for t in toks):
        raise AssertionError("serve: token outside the vocabulary")
    if summary["kv"]["mode"] != "paged":
        raise AssertionError(f"serve: kv mode {summary['kv']}")
    out = {"device": rank_device, "model": f"gpt-{sizes['serve_size']}",
           "dtype": "bfloat16", "kv": summary["kv"]["mode"],
           "requests": len(prompts),
           "prompt_lens": sizes["serve_prompts"],
           "tokens_returned": [len(t) for t in tokens],
           "round_secs": secs, "decode_steps": summary["steps"]}

    # The float32 comparison round, then generate in THIS process — the
    # rank has exited, so the chip is free again.
    import jax.numpy as jnp

    env32 = {"JAX_DEFAULT_MATMUL_PRECISION": "highest"}
    spec32 = dict(spec, overrides={"dtype": jnp.float32})
    prompt = rs.randint(0, vocab, sizes["oracle_prompt"]).tolist()
    steps = sizes["oracle_steps"]
    (served,), summary32, secs32 = _serve_round(
        spec32, env32, [prompt], [steps], timeout)

    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise AssertionError(
            "the ServeJob parent initialised a JAX backend while its rank "
            "held the chip")
    jax.config.update("jax_default_matmul_precision", "highest")
    own_device = _start_backend(rehearse)
    from horovod_tpu.models.decode import generate
    from horovod_tpu.models.transformer import gpt

    model = gpt(sizes["serve_size"], dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, min(8, model.cfg.max_len)), jnp.int32))
    want = np.asarray(generate(model.cfg, params,
                               jnp.asarray([prompt], jnp.int32),
                               steps))[0].tolist()
    out["generate_check"] = {
        "dtype": "float32 (pinned for this comparison only)",
        "served": served, "generate": want, "round_secs": secs32,
        "rank_device": summary32["device"], "own_device": own_device}
    if served != want:
        raise AssertionError(
            f"serve vs generate: served {served} != generate {want}")
    return out


def phase_dp4(rehearse: bool) -> dict:
    """Four chips, one process: the gpt-small step with
    DistributedOptimizer over hvd.mesh("flat"), against the same global
    batch and seed in a plain (un-shard_mapped) step on one of those
    chips; then one hierarchical_allreduce on a 2x2 mesh against psum."""
    device = _start_backend(rehearse)
    if device["count"] != 4:
        raise SystemExit(
            f"chip_smoke --chips 4: JAX reports {device['count']} devices")
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import gpt
    from horovod_tpu.parallel import hierarchical_allreduce
    from horovod_tpu.testing.steps import build_gpt_step

    sizes = _sizes(rehearse)
    size, _, seq = sizes["gpt"]
    per_chip = sizes["gpt_dp_batch"]
    step, state, static = build_gpt_step(
        size, "bf16", per_chip, seq, attention="flash")
    global_batch = static["global_batch"]
    tokens = state[2]

    def holders(x):
        return sorted(s.device.id for s in x.addressable_shards)

    batch_devices = holders(tokens)
    if len(set(batch_devices)) != 4:
        raise AssertionError(f"batch shards live on {batch_devices}")
    if {s.data.shape[0] for s in tokens.addressable_shards} != {per_chip}:
        raise AssertionError("batch is not split evenly over the chips")
    # Same global batch on one chip, fetched before the step donates.
    tokens_host = np.asarray(tokens)

    compiled, compile_secs = _compile(step, state)
    text = compiled.as_text()
    if "all-reduce" not in text:
        raise AssertionError("no all-reduce in the compiled DP step")
    if not rehearse and "tpu_custom_call" not in text:
        raise AssertionError("no tpu_custom_call in the compiled DP step")
    carry, losses4, secs4 = _run_steps(compiled, state, static["carry_len"],
                                       TRAIN_STEPS)
    _check_losses("dp4", losses4)
    leaf = jax.tree_util.tree_leaves(carry[0])[0]
    out_devices = holders(leaf)
    if len(set(out_devices)) != 4:
        raise AssertionError(f"output shards live on {out_devices}")
    del step, state, compiled, carry

    # The comparison: no mesh, no shard_map, no DistributedOptimizer.
    one = jax.devices()[0]
    model = gpt(size, dtype=jnp.bfloat16, max_len=seq,
                attention_impl="flash")
    toks1 = jax.device_put(tokens_host, one)
    params1 = jax.device_put(
        model.init(jax.random.PRNGKey(0), toks1[:2, :-1]), one)
    tx = optax.adamw(1e-4)
    opt1 = tx.init(params1)

    @jax.jit
    def plain_step(p, o, toks):
        def loss_fn(p):
            logits = model.apply(p, toks[:, :-1])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, toks[:, 1:]).mean()

        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    plain, compile_secs1 = _compile(plain_step, (params1, opt1, toks1))
    _, losses1, secs1 = _run_steps(plain, (params1, opt1, toks1), 2,
                                   TRAIN_STEPS)
    diff = max(abs(a - b) for a, b in zip(losses4, losses1))
    if not diff <= FOUR_VS_ONE_CHIP_ATOL:
        raise AssertionError(
            f"four-chip vs one-chip losses differ by {diff} "
            f"(> {FOUR_VS_ONE_CHIP_ATOL}): {losses4} vs {losses1}")

    # One hierarchical allreduce (reduce-scatter over local, psum over
    # cross, all-gather over local) against a plain psum over both axes.
    grid = np.asarray(jax.devices(), dtype=object).reshape(2, 2)
    mesh = Mesh(grid, (hvd.CROSS_AXIS, hvd.LOCAL_AXIS))
    both = (hvd.CROSS_AXIS, hvd.LOCAL_AXIS)
    x = np.random.RandomState(1).randn(4, 1 << 16).astype(np.float32)

    def reduce_both(v):
        h = hierarchical_allreduce(v[0], op=hvd.Sum)
        return h[None], jax.lax.psum(v[0], both)[None]

    hier, flat = jax.jit(jax.shard_map(
        reduce_both, mesh=mesh, in_specs=(P(both),),
        out_specs=(P(both), P(both)),
    ))(x)
    want = x.sum(axis=0)
    hier_err = float(np.abs(np.asarray(hier) - want).max())
    psum_err = float(np.abs(np.asarray(flat) - want).max())
    if not (np.allclose(np.asarray(hier), np.asarray(flat), atol=1e-4)
            and hier_err <= 1e-4):
        raise AssertionError(
            f"hierarchical_allreduce off by {hier_err} (psum {psum_err})")
    return {"device": device, "model": f"gpt-{size}",
            "global_batch": global_batch, "per_chip_batch": per_chip,
            "seq_len": seq, "batch_shard_devices": batch_devices,
            "output_shard_devices": out_devices, "all_reduce": True,
            "four_chips": {"compile_secs": compile_secs,
                           "step_secs": secs4, "losses": losses4},
            "one_chip": {"compile_secs": compile_secs1,
                         "step_secs": secs1, "losses": losses1},
            "four_vs_one_max_abs_diff": diff,
            "four_vs_one_atol": FOUR_VS_ONE_CHIP_ATOL,
            "hierarchical_allreduce_max_abs_err": hier_err}


# --------------------------------------------------------------- driver

def _run_phase_here(name: str, rehearse: bool, timeout: float) -> int:
    """Child entry: run one phase in this process; a failure propagates
    as a traceback and a non-zero exit."""
    if name == "serve":
        out = phase_serve(rehearse, timeout)
    else:
        out = {"resnet50": phase_resnet50, "gpt_small": phase_gpt_small,
               "dp4": phase_dp4}[name](rehearse)
    print(RESULT_TAG + json.dumps(out), flush=True)
    return 0


def _run_phase_child(name: str, rehearse: bool, chips: int,
                     timeout: float):
    """Run one phase as a child in its own process group and stop the
    whole group when it ends.  Returns the phase's result dict, or None
    if it failed."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    # The smoke's path is the jit path; nothing on it may load a native
    # library left on disk by an earlier build.
    env["HVDTPU_EAGER_ENGINE"] = "python"
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={chips}")
    cmd = [sys.executable, os.path.join(HERE, "chip_smoke.py"),
           "--phase", name, "--phase-timeout", str(int(timeout))]
    if rehearse:
        cmd.append("--rehearse-cpu")
    proc = subprocess.Popen(cmd, env=env, cwd=HERE, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    t_kill = time.monotonic() + timeout
    timer = threading.Timer(timeout, _kill_group, [proc.pid])
    timer.daemon = True
    timer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
    finally:
        timer.cancel()
        _kill_group(proc.pid)
    if time.monotonic() >= t_kill:
        print(f"# phase {name}: killed after {timeout:.0f}s", flush=True)
        return None
    if rc != 0:
        print(f"# phase {name}: exit code {rc}", flush=True)
        return None
    return result


def _kill_group(pgid: int) -> None:
    """Stop everything a phase started (its serving ranks included)."""
    try:
        os.killpg(pgid, signal.SIGKILL)
    except ProcessLookupError:
        pass


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=[1, 4],
                        help="4 runs only the four-chip data-parallel "
                        "phase and what it is compared with")
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="tiny sizes on CPU devices: proves the "
                        "control flow, never prints a chip result")
    parser.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    parser.add_argument("--phase-timeout", type=float, default=600.0,
                        help=argparse.SUPPRESS)
    args = parser.parse_args()

    if args.phase:
        return _run_phase_here(args.phase, args.rehearse_cpu,
                               args.phase_timeout)

    if not os.path.isdir(os.path.join(HERE, "horovod_tpu")):
        print("chip_smoke: horovod_tpu/ must sit beside this script",
              file=sys.stderr)
        return 2
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if not args.rehearse_cpu and platforms and \
            "tpu" not in platforms.split(","):
        print(f"chip_smoke: JAX_PLATFORMS={platforms!r} keeps JAX off the "
              "chip, and this script needs it.  --rehearse-cpu runs the "
              "tiny-size rehearsal instead.", file=sys.stderr)
        return 2

    t_end = time.monotonic() + TOTAL_BUDGET_SECS
    phases = FOUR_CHIP_PHASES if args.chips == 4 else ONE_CHIP_PHASES
    devices, failed = [], []
    for name in phases:
        left = t_end - time.monotonic()
        t0 = time.perf_counter()
        result = _run_phase_child(name, args.rehearse_cpu, args.chips,
                                  max(left, 1.0))
        line = {"phase": name, "ok": result is not None,
                "phase_secs": round(time.perf_counter() - t0, 1)}
        line.update(result or {})
        print(json.dumps(line), flush=True)
        if result is None:
            failed.append(name)
            break
        devices.append(result["device"])

    if failed:
        print(f"chip_smoke: FAILED in phase {failed[0]}", file=sys.stderr)
        return 1
    device = devices[0]
    if any(d != device for d in devices):
        print(f"chip_smoke: phases disagree on the device: {devices}",
              file=sys.stderr)
        return 1
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal": "passed", "device": device}),
              flush=True)
        return 0
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(f"chip_smoke: ran on {device}, wanted {args.chips} tpu "
              "device(s)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
