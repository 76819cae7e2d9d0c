"""Does the runtime's ``peak_bytes_in_use`` hold what a program takes while
it runs?  (ROADMAP A6.)  One jitted program with a temporary of known size
that cannot be fused away, on the chip:

    python3 benchmark/tools/memprobe.py

prints the counters before and after beside the compiler's
``memory_analysis()``.  If the peak rises by arguments + outputs alone,
the counter leaves temporaries out and ``harness.memory_peak`` has
to add them; if it rises by the temporaries too, it adds nothing.
"""

import json

import jax
import jax.numpy as jnp


def stats():
    s = jax.devices()[0].memory_stats() or {}
    return {k: int(s[k]) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit") if k in s}


def main() -> None:
    n = 16384                                   # one [n, n] bfloat16 array is 512 MiB
    print(json.dumps({"device": jax.devices()[0].device_kind, "start": stats()}))
    a = jnp.ones((n, n), jnp.bfloat16)
    a.block_until_ready()
    print(json.dumps({"after_argument": stats()}))

    def chain(x):                               # three products: two intermediates live at once
        y = jnp.tanh(x @ x)
        z = jnp.tanh(y @ x)
        return (z @ y).sum()

    compiled = jax.jit(chain).lower(a).compile()
    m = compiled.memory_analysis()
    print(json.dumps({"compiler": {"temp": int(m.temp_size_in_bytes), "argument": int(m.argument_size_in_bytes),
                                   "output": int(m.output_size_in_bytes)}}))
    compiled(a).block_until_ready()
    after = stats()
    print(json.dumps({"after_program": after,
                      "peak_minus_argument": after["peak_bytes_in_use"] - int(m.argument_size_in_bytes)}))


if __name__ == "__main__":
    main()
