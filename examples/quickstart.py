"""Quickstart: the paper in 60 seconds.

1. Define a binary neural network (the paper's headline config:
   32-bit activations, layers of 64 and 32 neurons).
2. Compile it with N2Net into an RMT switching-chip pipeline program.
3. Run packets through the simulated chip and check against the BNN oracle.
4. Print the throughput model and a P4 excerpt.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import bnn, compile_bnn, run_program, throughput
from repro.core.p4gen import generate_p4


def main():
    compile_cache.enable()
    spec = bnn.BnnSpec((32, 64, 32))     # dst-IP -> 64 -> 32 neurons
    params = bnn.init_params(spec, jax.random.PRNGKey(0))

    prog = compile_bnn([np.asarray(w) for w in params])
    print("== compiled pipeline ==")
    print(prog.summary())

    packets = jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, (8, 32)).astype(jnp.int32)
    y_chip = run_program(prog, packets)
    y_oracle = bnn.forward(params, packets)
    assert (np.asarray(y_chip) == np.asarray(y_oracle)).all()
    print(f"\nchip output == oracle for {packets.shape[0]} packets ✔")

    rep = throughput.report_for_program(prog)
    print(
        f"\nthroughput: {rep.networks_per_second:.3e} networks/s "
        f"({rep.elements_used}/{rep.elements_available} elements, "
        f"{rep.passes} pass) — paper claims 960e6"
    )

    p4 = generate_p4(prog)
    print("\n== P4 excerpt ==")
    print("\n".join(p4.splitlines()[:20]))
    print(f"... ({len(p4.splitlines())} lines total)")


if __name__ == "__main__":
    main()
