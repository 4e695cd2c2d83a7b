"""The mesh cases of ``test_mesh.py``, run in a process of their own that
sees four CPU devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      python perfbench/tests/mesh_cases.py <scratch dir>

It writes a checkout whose BENCHMARK.json holds one new four-chip cell of a
small NeMo-shaped configuration (data files only), runs it sound and with
faults, and prints one JSON object of readings as its last line.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CELL = "small-nemo.small-1x4"
SEED = 2 ** 31 + 23
# d 64, 4 query heads and 2 KV heads of 32 (head_dim is not d/heads),
# SwiGLU, RMSNorm: Mistral-NeMo's shape at a size a test run holds.  Its
# RMSNorm eps is 1e-6, the one value the program's RMSNorm takes
# (``repro.models.layers.apply_norm``); NeMo's own 1e-5 the program cannot
# run (PERF.md, Open questions).
SMALL_NEMO = {"name": "small-nemo", "num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
              "intermediate_size": 256, "vocab_size": 512, "norm_eps": 1e-6}
TRAFFIC = {"seq": 128, "batch": 1, "chunks": 4, "offload": True, "remat": "full", "mesh": "1x4"}
# The small cell's own limits.  Sound runs read up to 1.2e-4, 1.8e-3 and
# 2.1e-3 on the seeds tried; the fp8 control reads 3.2e-3, 1.6e-2 and
# 8.6e-3, the exchange left out 1.3e-2, 8.8e-2 and 3.4e-2, half of the
# batch left out 1.2e-2, 0.11 and 0.22, a state left unchanged 1 on both
# leaf gaps.
LIMITS = {"steps": 3, "loss_gap": 8e-4, "grad_gap": 6e-3, "update_gap": 5e-3}


def write_checkout(root: str) -> None:
    data = os.path.join(root, "perfbench")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(data, d), exist_ok=True)
    model = json.load(open(os.path.join(ROOT, "perfbench", "configs", "mistral-nemo-12b.json")))
    model.update(SMALL_NEMO)
    with open(os.path.join(data, "configs", "small-nemo.json"), "w") as f:
        json.dump(model, f)
    with open(os.path.join(data, "traffic", "small-1x4.json"), "w") as f:
        json.dump(TRAFFIC, f)
    with open(os.path.join(data, "limits", CELL + ".json"), "w") as f:
        json.dump(LIMITS, f)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"] = [{"name": "small-nemo", "source": "test", "reduced": [], "why": "test",
                         "file": "perfbench/configs/small-nemo.json"}]
    bench["workloads"] = [{"name": CELL, "config": "small-nemo", "traffic": "small-1x4",
                           "chips": 4, "why": "test"}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def main(root: str) -> dict:
    import jax

    from perfbench import check, faults, harness, program, reference, weights

    write_checkout(root)
    spec = harness.load_cell(root, CELL)
    model, traffic, steps = spec["model"], spec["traffic"], spec["limits"]["steps"]
    devices = jax.devices()[:4]
    out = {"devices": len(devices)}

    def run():
        r = harness.run(root, CELL, SEED, 0.5, False, time.perf_counter(), require_tpu=False)
        return {"correct": r["correct"], "attempted": r["attempted"], "failed": r["failed"],
                "metrics": sorted(r["metrics"]), "count": r["device"]["count"],
                "checked": {k: v["value"] for k, v in r["checked"].items()}}

    out["sound"] = run()
    for fault in sorted(faults.FAULTS):
        undo = faults.plant(fault)
        try:
            out[fault] = run()
        finally:
            undo()

    # the reference over four devices and over one, and its faults
    one, _ = harness.reference_readings(model, traffic, SEED, steps, devices[:1])
    four, _ = harness.reference_readings(model, traffic, SEED, steps, devices)
    out["four_vs_one"] = check.numbers(four, one)
    for mode in ("fp8", "no_exchange"):
        low, _ = harness.reference_readings(model, traffic, SEED, steps, devices, mode=mode)
        ok, shown = check.judge(check.numbers(low, four), spec["limits"])
        out[mode] = {"correct": ok, "checked": {k: v["value"] for k, v in shown.items()}}

    # the seed's weights under the program's shardings and unsharded
    tr = program.Trainer(model, traffic, SEED, devices)
    flat = jax.tree.leaves(jax.device_get(tr.params))
    plain = jax.tree.leaves(jax.device_get(weights.make(model, SEED)))
    ref_sh = jax.tree.leaves(jax.device_get(
        weights.make(model, SEED, out_shardings=reference.shardings(model, devices))))
    out["program_splits"] = any(not x.sharding.is_fully_replicated
                                for x in jax.tree.leaves(tr.params))
    for name, made in (("program", flat), ("reference", ref_sh)):
        out["same_bits_" + name] = all(a.dtype == b.dtype and a.tobytes() == b.tobytes()
                                       for a, b in zip(made, plain))
    return out


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1])), flush=True)
