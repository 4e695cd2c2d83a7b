#!/usr/bin/env python3
"""Does the device's ``peak_bytes_in_use`` count a program's temporaries?

  python3 perfbench/probe_hbm.py

Runs, in a fresh process, one jitted program whose only large buffer is a
4 GiB float32 temporary (kept whole by an optimization barrier) and prints
the compiler's ``memory_analysis`` beside ``peak_bytes_in_use`` before and
after the call.  If the peak rises by about 4 GiB, it counts temporaries.
"""
import json

import jax
import jax.numpy as jnp

N = 1 << 30  # float32 elements: 4 GiB


def probe(x):
    big = jax.lax.optimization_barrier(jnp.full((N,), x, jnp.float32))
    return jnp.sum(big * big)


def main():
    dev = jax.devices()[0]
    before = dev.memory_stats()["peak_bytes_in_use"]
    c = jax.jit(probe).lower(jnp.float32(1.5)).compile()
    ma = c.memory_analysis()
    out = float(c(jnp.float32(1.5)))
    after = dev.memory_stats()["peak_bytes_in_use"]
    print(json.dumps({"device": dev.device_kind, "result": out, "temp_bytes": ma.temp_size_in_bytes,
                      "peak_before": before, "peak_after": after,
                      "rise_gib": (after - before) / 2 ** 30}))


if __name__ == "__main__":
    main()
