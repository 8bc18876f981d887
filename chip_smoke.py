"""Smoke run of TonY's training path on TPU chips, at Qwen3-1.7B widths.

  python chip_smoke.py             # one chip: TonY train job + kernels
  python chip_smoke.py --chips 4   # four chips: sharded TonY job vs one device

Everything runs in this one process: the TonY executors are threads, and the
chief worker's thread drives the chip. The model has every published width of
``qwen3-1.7b`` and is cut in depth only; weights are random from a seed.

One chip: a job goes client -> RM -> AM -> executors -> jitted train step,
with checkpoints, a seeded kill of the chief and a relaunch that resumes from
the committed checkpoint. Then the rmsnorm and flash-attention Pallas kernels
run compiled and are compared with their float32 references.

Four chips: the same TonY train program on the four-device mesh, compared
with the same steps on a one-device mesh in this process.

The last line of standard output is ``{"ok": true, "device": {...}}``. Any
failed check or phase exits 1 without it; so does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (  # noqa: E402
    EventLog,
    FaultInjector,
    FaultKind,
    FaultPlan,
    FaultSpec,
    TonYClient,
    YarnLikeBackend,
    format_failure_report,
    job_spec_from_props,
    make_cluster,
)
from repro.data import make_dataset  # noqa: E402
from repro.distributed.sharding import to_shardings  # noqa: E402
from repro.distributed.steps import (  # noqa: E402
    abstract_train_state,
    init_train_state,
    make_train_fn,
)
from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.rmsnorm import rmsnorm  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.programs import make_train_program  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402

ARCH = "qwen3-1.7b"
# Depth cut: 4 of 28 layers, B=4, T=2048. Full depth needs 19 GiB for float32
# weights and two AdamW moments alone; this cut compiles to 5.73 GiB of
# arguments plus 4.05 GiB of temporaries, 66% of a v5e chip's 16 GB.
LAYERS, BATCH, SEQ = 4, 4, 2048
STEPS, CKPT_EVERY, KILL_AT = 6, 3, 4
FOUR_CHIP_STEPS = 3
LR = 1e-3                     # make_train_program's default
SEED = 0
HBM_BYTES = 16e9              # one v5e chip
BF16_ULP = 2.0 ** -7          # bfloat16 spacing, relative to the value, at most
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Checks:
    """Prints each check as it is made and remembers the ones that failed."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)


def bytes_in_use(devices) -> list[int]:
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


class Recorder:
    """What the chief reports during a TonY job, keyed by attempt: losses,
    device bytes at the start of each attempt and after its first step, and
    each backend compile with whether the persistent cache served it."""

    def __init__(self, devices):
        self.devices = devices
        self.attempt = 0
        self.losses: dict[int, dict[int, float]] = {}
        self.first_step_bytes: dict[int, list[int]] = {}
        self.compiles: list[tuple[int, str, float, bool]] = []
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self._hit = True

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append((self.attempt, str(kw.get("fun_name")), secs,
                                  self._hit))
            self._hit = False

    def wrap(self, inner):
        def program(env, ctx):
            if env["TASK_TYPE"] == "worker" and env["TASK_INDEX"] == "0":
                self.attempt = int(ctx.shared.get("attempt", 1))
                print(f"attempt {self.attempt}: bytes_in_use at start "
                      f"{[gib(b) for b in bytes_in_use(self.devices)]}",
                      flush=True)
            return inner(env, ctx)
        return program

    def on_step(self, step: int, metrics: dict) -> None:
        mine = self.losses.setdefault(self.attempt, {})
        if not mine:
            self.first_step_bytes[self.attempt] = bytes_in_use(self.devices)
        mine[step] = metrics["loss"]
        print(f"attempt {self.attempt} step {step}: loss {metrics['loss']!r}",
              flush=True)

    def print_compiles(self) -> None:
        for attempt, name, secs, hit in self.compiles:
            print(f"  attempt {attempt}: compile {name} {secs:.2f} s"
                  f"{' (persistent cache hit)' if hit else ''}")


def run_tony_job(cfg, rec: Recorder, *, steps: int, plan: FaultPlan,
                 max_attempts: int, ckpt_dir: str):
    events = EventLog()
    rm = make_cluster(event_log=events,
                      chaos=FaultInjector(plan, events=events))
    client = TonYClient(YarnLikeBackend(rm))
    job = job_spec_from_props({
        "tony.application.name": f"chip-smoke-{cfg.name}",
        "tony.application.max-attempts": str(max_attempts),
        "tony.worker.instances": "2",
        "tony.worker.memory": "8192",
        "tony.worker.vcores": "4",
        "tony.worker.gpus": "1",
        "tony.worker.node-label": "gpu",
    })
    prog = make_train_program(cfg, steps=steps, batch_size=BATCH, seq_len=SEQ,
                              ckpt_dir=ckpt_dir, ckpt_every=CKPT_EVERY, lr=LR,
                              data_seed=SEED, on_step=rec.on_step)
    t0 = time.monotonic()
    result = client.run_and_wait(job, rec.wrap(prog), timeout=900)
    print(f"job {result.final_status} in {len(result.attempts)} attempt(s), "
          f"{time.monotonic() - t0:.1f} s; resumed {result.resumed_attempts}",
          flush=True)
    for ev in events.of_kind("ckpt_committed"):
        print(f"  checkpoint step {ev.payload['step']} committed: "
              f"{gib(ev.payload['bytes'])} in {ev.payload['duration_s']:.1f} s")
    if not result.succeeded:
        print(format_failure_report(result))
    return result


def one_chip_mesh(device) -> jax.sharding.Mesh:
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=[device])


def step_memory(cfg, device) -> None:
    """Compile the job's step again (a cache hit) for its memory analysis."""
    mesh = one_chip_mesh(device)
    with jax.set_mesh(mesh):
        step, pspecs = make_train_fn(
            cfg, mesh, "fsdp_tp", opt=AdamWConfig(lr=LR, weight_decay=0.0))
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            abstract_train_state(cfg), to_shardings(pspecs, mesh))
        tok = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
        mem = step.lower(state, {"tokens": tok, "labels": tok}
                         ).compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"step memory_analysis: {mem}")
    print(f"  arguments {gib(mem.argument_size_in_bytes)} + temporaries "
          f"{gib(mem.temp_size_in_bytes)} = {gib(used)}, "
          f"{100 * used / HBM_BYTES:.1f}% of 16 GB")


def state_bytes(cfg) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(abstract_train_state(cfg)))


def describe(cfg) -> None:
    full = get_config(ARCH)
    print(f"config {cfg.name} ({cfg.source}): d_model {cfg.d_model}, heads "
          f"{cfg.num_heads}/{cfg.num_kv_heads} kv, head_dim {cfg.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}")
    print(f"cut: {cfg.num_layers} of {full.num_layers} layers, widths as "
          f"published; batch {BATCH} x {SEQ} tokens; train state "
          f"{gib(state_bytes(cfg))}")


def train_phase(cfg, check: Checks) -> None:
    """One chip: a TonY job killed once and resumed from its checkpoint."""
    dev = jax.devices()[:1]
    rec = Recorder(dev)
    plan = FaultPlan(seed=SEED).add(
        FaultSpec(FaultKind.KILL_TASK, task="worker:0", at_step=KILL_AT))
    ckpt_dir = tempfile.mkdtemp(prefix="chip-smoke-ckpt-")
    print(f"checkpoints in a temporary directory with "
          f"{gib(shutil.disk_usage(ckpt_dir).free)} free", flush=True)
    try:
        result = run_tony_job(cfg, rec, steps=STEPS, plan=plan, max_attempts=2,
                              ckpt_dir=ckpt_dir)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    rec.print_compiles()
    for attempt, b in rec.first_step_bytes.items():
        print(f"attempt {attempt}: bytes_in_use after its first step "
              f"{[gib(x) for x in b]}")

    check(result.succeeded and len(result.attempts) == 2,
          "job SUCCEEDED in 2 attempts")
    check(result.resumed_attempts == {2: CKPT_EVERY},
          f"attempt 2 resumed from committed step {CKPT_EVERY}")
    losses = [v for per in rec.losses.values() for v in per.values()]
    check(bool(losses) and all(math.isfinite(v) for v in losses),
          f"all {len(losses)} losses finite")
    first = rec.losses.get(1, {}).get(0, math.nan)
    # random init gives near-uniform logits; a loss this far from ln(V) means
    # wrong weights or labels
    check(abs(first - math.log(cfg.vocab_size)) < 0.25,
          f"first loss {first!r} within 0.25 of ln(V) = "
          f"{math.log(cfg.vocab_size):.4f}")
    a1, a2 = rec.losses.get(1, {}), rec.losses.get(2, {})
    again = sorted(set(a1) & set(a2))
    # same executable, same restored bits, same batch_at(step): bit-equal
    check(bool(again) and all(a1[s] == a2[s] for s in again),
          f"recomputed steps {again} give bit-equal losses "
          f"{[(a1[s], a2[s]) for s in again]}")
    steps_a2 = [c for c in rec.compiles if c[0] == 2 and "train_step" in c[1]]
    check(bool(steps_a2) and steps_a2[0][3],
          "attempt 2's train step came from the persistent compile cache")
    step_memory(cfg, dev[0])


def kernel_phase(cfg, check: Checks) -> None:
    """rmsnorm and the flash-attention forward, compiled, at model widths."""
    kq, kk, kv, kx, ks = jax.random.split(jax.random.PRNGKey(SEED), 5)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jax.random.normal(kq, (1, SEQ, H, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (1, SEQ, KV, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (1, SEQ, KV, hd), jnp.bfloat16)
    x = jax.random.normal(kx, (BATCH * SEQ, cfg.d_model), jnp.bfloat16)
    s = jax.random.normal(ks, (cfg.d_model,), jnp.float32) * 0.1
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        want_attn = ref.flash_attention_ref(f32(q), f32(k), f32(v), causal=True)
        want_norm = ref.rmsnorm_ref(f32(x), s)

    got = f32(flash_attention(q, k, v, causal=True, interpret=False))
    err = jnp.abs(got - want_attn)
    # the bf16 output rounds once (half an ulp of |o|), and p @ v may round
    # the f32 probabilities to bf16 (half an ulp of max|v|): 2x headroom
    bound = BF16_ULP * (jnp.abs(want_attn) + jnp.max(jnp.abs(f32(v))))
    check(bool(jnp.all(err <= bound)),
          f"flash_attention (1, {SEQ}, {H}/{KV}, {hd}) causal vs f32 ref: "
          f"max |err| {float(jnp.max(err))!r}, max err/bound "
          f"{float(jnp.max(err / bound))!r}")

    got = f32(rmsnorm(x, s, interpret=False))
    err = jnp.abs(got - want_norm)
    # both compute in f32 and the kernel rounds to bf16 once: half an ulp,
    # or one ulp where the two f32 values straddle a rounding boundary
    bound = BF16_ULP * jnp.abs(want_norm) + 1e-6
    check(bool(jnp.all(err <= bound)),
          f"rmsnorm ({BATCH * SEQ}, {cfg.d_model}) vs f32 ref: max |err| "
          f"{float(jnp.max(err))!r}, max err/bound "
          f"{float(jnp.max(err / bound))!r}")


def four_chip_phase(cfg, check: Checks) -> None:
    """The TonY program sharded over four chips against one chip."""
    devs = jax.devices()[:4]
    rec = Recorder(devs)
    ckpt_dir = tempfile.mkdtemp(prefix="chip-smoke-ckpt-")
    try:
        result = run_tony_job(cfg, rec, steps=FOUR_CHIP_STEPS,
                              plan=FaultPlan(seed=SEED), max_attempts=1,
                              ckpt_dir=ckpt_dir)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    rec.print_compiles()
    check(result.succeeded, "four-chip TonY job SUCCEEDED")
    sharded = [rec.losses.get(1, {}).get(s, math.nan)
               for s in range(FOUR_CHIP_STEPS)]
    held = rec.first_step_bytes.get(1, [0] * len(devs))
    quarter = state_bytes(cfg) / len(devs)
    print(f"four chips: bytes_in_use after the first step "
          f"{[gib(b) for b in held]}; a quarter of the state is {gib(quarter)}")
    check(all(abs(b - quarter) <= 0.1 * quarter for b in held),
          "each chip holds a quarter of the state, within 10%")

    mesh = one_chip_mesh(devs[0])
    data = make_dataset("synthetic", BATCH, SEQ, cfg.vocab_size, seed=SEED)
    with jax.set_mesh(mesh):
        step, pspecs = make_train_fn(
            cfg, mesh, "fsdp_tp", opt=AdamWConfig(lr=LR, weight_decay=0.0))
        state = jax.jit(functools.partial(init_train_state, cfg),
                        out_shardings=to_shardings(pspecs, mesh)
                        )(jax.random.PRNGKey(0))
        single = []
        for s in range(FOUR_CHIP_STEPS):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(s).items()}
            state, metrics = step(state, batch)
            single.append(float(metrics["loss"]))
            if s == 0:
                print(f"one chip: bytes_in_use after the first step "
                      f"{gib(bytes_in_use(devs[:1])[0])}")
    del state
    print(f"losses, four chips: {sharded}")
    print(f"losses, one chip:   {single}")
    # sharding reorders the bf16 partial sums, so the two agree to bf16
    # precision (half an ulp), not to the bit
    check(all(abs(a - b) <= BF16_ULP / 2 * abs(b)
              for a, b in zip(sharded, single)),
          f"losses agree within 2^-8 relative: max |diff| "
          f"{max(abs(a - b) for a, b in zip(sharded, single))!r}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded four-chip comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devices[0].platform!r}); refusing to run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {devices[0].device_kind}, count {len(devices)}, "
          f"bytes_limit {gib(devices[0].memory_stats()['bytes_limit'])}")
    print(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH).replace(num_layers=LAYERS)
    describe(cfg)

    check = Checks()
    phases = ([four_chip_phase] if args.chips == 4
              else [train_phase, kernel_phase])
    for phase in phases:
        print(f"--- {phase.__name__}", flush=True)
        t0 = time.monotonic()
        try:
            phase(cfg, check)
        except Exception:  # noqa: BLE001 - report the phase, run the rest
            traceback.print_exc()
            check(False, f"{phase.__name__} raised")
        print(f"--- {phase.__name__} done in {time.monotonic() - t0:.1f} s")
    for i, d in enumerate(devices[:args.chips]):
        print(f"device {i}: peak_bytes_in_use "
              f"{gib(d.memory_stats()['peak_bytes_in_use'])}")
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
