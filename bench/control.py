"""Readings that set a cell's limits: the control and the planted faults.

  python3 bench/control.py --workload <cell> --seeds 11,12,13

For each seed, on the chip and at the cell's own sizes, the configuration's
reference trains the cell's compared steps four times besides its float32
run, and each is compared with the float32 run by the cell's numbers:

* ``control``: the reference with every matrix product in fp8 (e4m3
  forward, e5m2 backward, per-tensor scales), the nearest precision below
  the configuration's bfloat16, standing in the program's place;
* ``half_batch``: the batch's second half left out, the mean taken over
  the rest;
* ``unchanged``: a step that returns its state unchanged: the losses of the
  initial weights on each batch (the reference at learning rate 0), and
  neither a first moment nor a change, so its gradient and change norms
  read 0 and their gaps 1.

The benchmark's own runs do not run this. It prints one JSON line per seed
and writes them to ``chiprun_out/bench/control-<cell>.jsonl``.
"""
import argparse
import json
import sys
import time

# the benchmark's command sets the environment (compile cache, TPU logs) as
# it is imported, before anything imports JAX
from run import ROOT


def readings(cell, seed: int) -> dict:
    from benchlib import check, traffic as gen
    from benchlib.harness import OPT, reference_run
    from benchlib.spec import load_json

    compared = int(load_json(ROOT / "bench" / "limits" /
                             f"{cell.name}.json")["compared_steps"])
    tokens = gen.make_tokens(cell.traffic, cell.config["vocab_size"], seed)
    ref = reference_run(cell, tokens, compared)
    out = {"seed": seed}
    t = time.monotonic()
    control = reference_run(cell, tokens, compared, matmul="fp8")
    out["control_s"] = time.monotonic() - t
    half = cell.traffic["batch"] // 2
    halved = reference_run(cell, tokens, compared, rows=slice(0, half))
    still = reference_run(cell, tokens, compared, opt=dict(OPT, lr=0.0))
    unchanged = {"losses": still["losses"],
                 "grad0_norms": dict.fromkeys(ref["grad0_norms"], 0.0),
                 "change_norms": dict.fromkeys(ref["change_norms"], 0.0)}
    out["reference_losses"] = ref["losses"]
    for name, run in (("control", control), ("half_batch", halved),
                      ("unchanged", unchanged)):
        out[name] = check.numbers(run, ref)
        out[name + "_diagnostics"] = check.diagnostics(run, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))

    from benchlib.device import NoChip, chips_for, describe
    from benchlib.spec import load_cell

    cell = load_cell(args.workload)
    try:
        devices = chips_for(cell.chips)
    except NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    from benchlib.harness import enable_compile_cache

    enable_compile_cache()
    out = ROOT / "chiprun_out" / "bench" / f"control-{cell.name}.jsonl"
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            r = readings(cell, seed)
            r["device"] = describe(devices)
            print(json.dumps(r), flush=True)
            f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
