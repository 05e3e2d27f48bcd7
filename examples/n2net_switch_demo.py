"""End-to-end driver for the paper's first use case: an in-network DoS
white/blacklist classifier.

Pipeline:
  1. generate a labelled dataset of 104-bit packet 5-tuples (padded to 128);
  2. train a BNN (128 -> 64 -> 32 -> 2) with the straight-through estimator
     on latent weights (BinaryNet-style) in pure JAX;
  3. export {0,1} weights, compile with the N2Net compiler (both the
     standard RMT chip and the §3 native-POPCNT variant);
  4. classify a held-out packet stream on the simulated chip, verify
     bit-exact agreement with the model, report accuracy + ASIC throughput;
  5. emit the P4 program.

Run:  PYTHONPATH=src python examples/n2net_switch_demo.py
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs.n2net_paper import FIVE_TUPLE
from repro.core import bitops, bnn, compile_bnn, throughput
from repro.core.interpreter import run_program_jit
from repro.core.p4gen import generate_p4
from repro.core.pipeline import RMT_NATIVE_POPCNT
from repro.kernels.ops import ste_sign


def make_dataset(key, n, bits=128):
    """Blacklist = membership in a union of masked prefixes (realistic ACL)."""
    k1, k2, k3 = jax.random.split(key, 3)
    pkts = jax.random.bernoulli(k1, 0.5, (n, bits)).astype(jnp.int32)
    n_rules = 12
    prefixes = jax.random.bernoulli(k2, 0.5, (n_rules, bits)).astype(jnp.int32)
    masks = (jax.random.uniform(k3, (n_rules, bits)) < 0.12).astype(jnp.int32)
    # packet matches rule r if it agrees with prefix r on all masked bits
    agree = 1 - jnp.bitwise_xor(pkts[:, None, :], prefixes[None])
    hit = jnp.all(jnp.where(masks[None].astype(bool), agree, 1), axis=-1)
    labels = jnp.any(hit, axis=-1).astype(jnp.int32)  # 1 = blacklisted
    return pkts, labels


def train_bnn(pkts, labels, sizes, steps, lr=0.05, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(sizes) - 1)
    ws = [
        jax.random.normal(k, (o, i)) * 0.3
        for k, i, o in zip(keys, sizes[:-1], sizes[1:])
    ]
    x = bitops.bits_to_sign(pkts)
    y = jax.nn.one_hot(labels, sizes[-1]) * 2 - 1

    def fwd(ws, x):
        h = x
        for w in ws[:-1]:
            h = ste_sign(h @ ste_sign(w).T)
        return h @ ste_sign(ws[-1]).T

    def loss(ws):
        return jnp.mean(jax.nn.relu(1.0 - y * fwd(ws, x)))

    @jax.jit
    def step(ws):
        l, gs = jax.value_and_grad(loss)(ws)
        return l, [w - lr * g for w, g in zip(ws, gs)]

    for i in range(steps):
        l, ws = step(ws)
        if i % max(1, steps // 5) == 0:
            print(f"  step {i:4d}  hinge loss {float(l):.4f}")
    return ws


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--train-size", type=int, default=2048)
    ap.add_argument("--test-size", type=int, default=2048)
    ap.add_argument("--p4-out", default="/tmp/n2net_dos_classifier.p4")
    args = ap.parse_args()

    sizes = FIVE_TUPLE.layer_sizes  # (128, 64, 32, 2)
    print(f"== training BNN {sizes} on synthetic ACL data ==")
    ptrain, ltrain = make_dataset(jax.random.PRNGKey(0), args.train_size)
    ptest, ltest = make_dataset(jax.random.PRNGKey(0), args.test_size)
    latent = train_bnn(ptrain, ltrain, sizes, args.steps)

    weights = [np.asarray(bitops.sign_to_bits(w)) for w in latent]
    model_params = [jnp.asarray(w) for w in weights]

    print("\n== compiling to the RMT pipeline ==")
    prog = compile_bnn(weights)
    print(prog.summary())

    chip_logits = run_program_jit(prog, ptest)
    model_logits = bnn.forward(model_params, ptest)
    assert (np.asarray(chip_logits) == np.asarray(model_logits)).all()
    pred = np.asarray(chip_logits)
    # argmax over the 2 output bits; tie -> class 0 ( bit ordering)
    yhat = (pred[:, 1] > pred[:, 0]).astype(int)
    acc = float((yhat == np.asarray(ltest)).mean())
    print(f"\nchip == model bit-exact ✔   held-out accuracy: {acc:.3f}")

    rep = throughput.report_for_program(prog)
    print(
        f"ASIC model: {rep.packets_per_second:.3e} packets/s "
        f"({rep.passes} pass(es), {rep.elements_used} elements)"
    )

    prog_np = compile_bnn(weights, RMT_NATIVE_POPCNT)
    rep_np = throughput.report_for_program(prog_np)
    print(
        f"§3 native-POPCNT chip: {rep_np.elements_used} elements "
        f"({rep.elements_used} on standard RMT), "
        f"{rep_np.packets_per_second:.3e} packets/s"
    )

    # software simulation rate, for context
    t0 = time.perf_counter()
    run_program_jit(prog, ptest).block_until_ready()
    dt = time.perf_counter() - t0
    print(f"(JAX chip-simulator: {args.test_size/dt:.3e} packets/s on CPU)")

    with open(args.p4_out, "w") as f:
        f.write(generate_p4(prog, name="dos_classifier"))
    print(f"\nP4 written to {args.p4_out}")


if __name__ == "__main__":
    main()
