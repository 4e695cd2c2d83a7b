#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process.

  python3 perfbench/calibrate.py --workload gpt-6.7b.short-8k \
      --seeds 1,2,3 --control-seeds 4,5,6 --fault-seeds 7,8,9 --out readings.jsonl

For each seed it prints (and appends to ``--out``) one JSON line with the
three compared numbers (``check.py``) of:
  * ``program``: the program's checked steps against the reference, as a run
    of ``run.py`` compares them (no window);
  * ``control``: the reference computed in float8 (``reference.py``
    ``mode="fp8"``) in the program's place;
  * ``no_exchange`` (cells on several chips, with each control seed): the
    reference with its layers' partial outputs not summed over the chips
    (``mode="no_exchange"``) in the program's place;
  * ``half_batch``: the program with half of each batch left out
    (``faults.py``).
Each is held against the float32 reference of its seed, computed once per
seed.  A state left unchanged reads 1 on ``update_gap`` by construction and
needs no run.  Needs the cell's chips, like ``run.py``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text):
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import gc

    import jax

    from perfbench import check, faults, harness, program, reference, weights

    spec = harness.load_cell(ROOT, args.workload)
    model, traffic = spec["model"], spec["traffic"]
    steps = spec["limits"]["steps"]
    devices = harness._devices(spec["cell"]["chips"], require_tpu=True)
    harness.configure_jax(ROOT)

    trainers, refs = {}, {}

    def fp32_ref(seed):
        if seed not in refs:
            refs[seed], _ = harness.reference_readings(model, traffic, seed, steps, devices)
            gc.collect()
        return refs[seed]

    def program_run(seed, fault=None):
        """The program's checked steps from the seed: the compiled step is
        built once per fault (or none) and reloaded with each seed's state."""
        if fault not in trainers:
            undo = faults.plant(fault) if fault else (lambda: None)
            try:
                trainers[fault] = program.Trainer(model, traffic, seed, devices)
                trainers[fault].compile(harness.TR.batch(traffic, model["vocab_size"], seed, 0))
            finally:
                undo()
        tr = trainers[fault]
        tr.load(seed)
        prog, snap = harness.checked_steps(
            tr, harness.stepper(tr, traffic, model["vocab_size"], seed), steps)
        tr.params = tr.opt_state = None
        gc.collect()
        ref = fp32_ref(seed)
        p0 = weights.make(model, seed, out_shardings=reference.shardings(model, devices))
        prog["update_norms"] = harness.update_norms(snap, p0)
        return [("program" if fault is None else fault, prog, ref)]

    def drop_references():
        """Each reference's programs leave the device before the next loads."""
        reference._make_step.cache_clear()
        jax.clear_caches()
        gc.collect()

    def control_run(seed):
        ref = fp32_ref(seed)
        out = []
        for mode in ["fp8"] + (["no_exchange"] if len(devices) > 1 else []):
            drop_references()
            got, _ = harness.reference_readings(model, traffic, seed, steps, devices, mode=mode)
            out.append(("control" if mode == "fp8" else mode, got, ref))
        drop_references()
        return out

    def fault_run(seed):
        return program_run(seed, fault="half_batch")

    jobs = ([(s, program_run) for s in args.seeds]
            + [(s, control_run) for s in args.control_seeds]
            + [(s, fault_run) for s in args.fault_seeds])
    with open(args.out, "a") as out:
        for seed, fn in jobs:
            t = time.perf_counter()
            for kind, got, ref in fn(seed):
                line = {"workload": args.workload, "kind": kind, "seed": seed,
                        "seconds": time.perf_counter() - t, "losses": got["losses"],
                        "ref_losses": ref["losses"], **check.numbers(got, ref)}
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
