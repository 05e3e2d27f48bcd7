"""Generative differential fuzzing: packed == fused == interpreter == oracle.

The credibility test for the bit-packed PHV executor (and the opcode-run
op-table scan it rode in with): random BNN programs — layer widths including
non-multiples of 32, learned SIGN thresholds across the full legal range,
folding — run through every executor backend and checked bit-for-bit against
the interpreter, the ``bnn.forward`` oracle, and (for default thresholds)
the STE trainer's forward.  Edge cases the generator might under-sample
(popcount ties, all-zero/all-one PHVs, extreme widths) are pinned
deterministically alongside.

Runs under real ``hypothesis`` when installed, else the seeded-random stub
(``tests/_hypothesis_stub.py``).  ``FUZZ_EXAMPLES`` scales the example
count (CI pins 200); failing case reprs land in ``$FUZZ_ARTIFACT_DIR``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from strategies import (
    HEAVY_EXAMPLES,
    ProgramCase,
    TenantMixCase,
    artifact_on_failure,
    build_case,
    chip_specs,
    given,
    mix_traffic,
    packets_for,
    program_cases,
    settings,
    st,
    stream_plans,
    tenant_mixes,
)

from repro.core import bitops, bnn, interpreter
from repro.core.compiler import compile_bnn
from repro.core.pipeline import ProgramConstraintError
from repro.dataplane import executor
from repro.dataplane.lowering import pack_bit_rows
from repro.dataplane.multitenant import AdmissionError, SwitchScheduler
from repro.train import bnn_trainer

BACKENDS = ("jnp", "pallas", "packed")


def _oracle(built, packets: np.ndarray) -> np.ndarray:
    return np.asarray(
        bnn.forward(
            [np.asarray(w) for w in built.params],
            packets,
            thresholds=built.thresholds,
        )
    )


def _assert_all_backends(built, packets: np.ndarray) -> None:
    """Every executor backend == interpreter == oracle on these packets."""
    oracle = _oracle(built, packets)
    interp = np.asarray(interpreter.run_program(built.program, packets))
    np.testing.assert_array_equal(interp, oracle)
    for backend in BACKENDS:
        out = executor.execute(built.lowered, packets, backend=backend)
        np.testing.assert_array_equal(
            out, oracle, err_msg=f"backend {backend!r} diverges from oracle"
        )


# ---------------------------------------------------------------------------
# The headline property: random programs, every backend, bit-exact
# ---------------------------------------------------------------------------

@given(program_cases())
def test_fuzz_backends_match_oracle(case: ProgramCase):
    with artifact_on_failure("fuzz_backends_match_oracle", case):
        built = build_case(case)
        packets = packets_for(case, seed=case.weight_seed ^ 0x5EED, n=40)
        _assert_all_backends(built, packets)


@given(program_cases())
def test_fuzz_ste_forward_matches_packed(case: ProgramCase):
    """The STE trainer's forward (the deploy-path witness) agrees with the
    packed executor for default thresholds — the only regime the trainer
    models."""
    if case.threshold_mode != "default":
        case = ProgramCase(
            case.layer_sizes, case.weight_seed, "default", case.threshold_seed
        )
    with artifact_on_failure("fuzz_ste_forward_matches_packed", case):
        built = build_case(case)
        packets = packets_for(case, seed=case.threshold_seed ^ 0x57E, n=32)
        latent = [
            np.asarray(bitops.bits_to_sign(w, np.float32))
            for w in built.params
        ]
        ste = np.asarray(bnn_trainer.forward_bits(latent, packets))
        packed = executor.execute(built.lowered, packets, backend="packed")
        np.testing.assert_array_equal(packed, ste)


@given(program_cases(), stream_plans())
@settings(max_examples=HEAVY_EXAMPLES)
def test_fuzz_chunking_invariance_and_resume(case: ProgramCase, plan):
    """Chunked execution and mid-stream resume never change any bit: one
    shot == chunked execute == a stream stopped and resumed mid-way."""
    n, chunk, seed = plan
    with artifact_on_failure(
        "fuzz_chunking_invariance_and_resume", (case, plan)
    ):
        built = build_case(case)
        packets = packets_for(case, seed=seed, n=n)
        one_shot = executor.execute(built.lowered, packets, backend="packed")
        np.testing.assert_array_equal(one_shot, _oracle(built, packets))
        for backend in BACKENDS:
            chunked = executor.execute(
                built.lowered, packets, backend=backend, chunk_size=chunk
            )
            np.testing.assert_array_equal(chunked, one_shot)
            # Mid-stream resume: feed the same packets as two separate
            # streams split at an uneven point; concatenated outputs must
            # equal the uninterrupted run.
            cut = max(1, n // 3)
            first = executor.execute_stream(
                built.lowered,
                [packets[:cut]],
                backend=backend,
                chunk_size=chunk,
                collect=True,
            )
            second = executor.execute_stream(
                built.lowered,
                [packets[cut:]],
                backend=backend,
                chunk_size=chunk,
                collect=True,
            )
            resumed = np.concatenate(
                [first.outputs, second.outputs]
            ).astype(np.int32)
            np.testing.assert_array_equal(resumed, one_shot)


@given(program_cases(max_layers=2, max_width=24), chip_specs())
@settings(max_examples=HEAVY_EXAMPLES)
def test_fuzz_chip_budgets_compile_or_reject(case: ProgramCase, chip):
    """A random chip budget either compiles the program — then it must be
    bit-exact — or rejects it with the typed constraint error.  Never a
    silent wrong answer."""
    with artifact_on_failure(
        "fuzz_chip_budgets_compile_or_reject", (case, chip)
    ):
        built = build_case(case)  # reference build on the default chip
        try:
            prog = compile_bnn(
                built.params, chip, thresholds=built.thresholds
            )
        except ProgramConstraintError:
            return
        packets = packets_for(case, seed=case.weight_seed ^ 0xC41B, n=24)
        oracle = _oracle(built, packets)
        lp = prog.lower()
        for backend in BACKENDS:
            out = executor.execute(lp, packets, backend=backend)
            np.testing.assert_array_equal(out, oracle)


# ---------------------------------------------------------------------------
# Deterministic edges the generator may under-sample
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_in", [2, 4, 31, 32, 33, 64])
def test_popcount_tie_resolution(n_in):
    """Agreement exactly at / one below the default ceil(n/2) threshold: the
    tie must resolve to 1 on every backend, exactly as the oracle does."""
    thr = (n_in + 1) // 2
    w = np.zeros((2, n_in), np.int32)
    w[:, :] = 0
    x = np.zeros((2, n_in), np.int32)
    # Packet 0: exactly thr agreements (tie -> fire).  Packet 1: thr - 1.
    x[0, thr:] = 1   # n_in - thr disagreements -> thr agreements
    x[1, thr - 1:] = 1
    prog = compile_bnn([w])
    built_oracle = np.asarray(bnn.forward([w], x))
    assert built_oracle[0, 0] == 1 and built_oracle[1, 0] == 0
    lp = prog.lower()
    for backend in BACKENDS:
        np.testing.assert_array_equal(
            executor.execute(lp, x, backend=backend), built_oracle
        )


@pytest.mark.parametrize(
    "sizes", [(1, 1), (32, 64, 32), (33, 65, 31), (31, 97, 5), (48, 48)]
)
@pytest.mark.parametrize("fill", [0, 1])
def test_all_zero_all_one_phvs(sizes, fill):
    case = ProgramCase(sizes, weight_seed=99, threshold_mode="default",
                       threshold_seed=0)
    built = build_case(case)
    packets = np.full((8, sizes[0]), fill, np.int32)
    _assert_all_backends(built, packets)


@pytest.mark.parametrize(
    "sizes", [(33, 65, 31), (1, 2, 1), (47, 33), (17, 33, 5)]
)
def test_widths_not_divisible_by_32(sizes):
    case = ProgramCase(sizes, weight_seed=7, threshold_mode="per_neuron",
                       threshold_seed=11)
    built = build_case(case)
    packets = packets_for(case, seed=3, n=48)
    _assert_all_backends(built, packets)


def test_threshold_extremes_never_and_always_fire():
    """thr = 0 fires on every packet, thr = n_in + 1 on none — on all
    backends, matching the oracle."""
    w = np.asarray(
        np.random.default_rng(5).integers(0, 2, (6, 20)), np.int32
    )
    thresholds = [np.array([0, 21, 10, 0, 21, 1], np.int32)]
    prog = compile_bnn([w], thresholds=thresholds)
    packets = np.asarray(
        np.random.default_rng(6).integers(0, 2, (32, 20)), np.int32
    )
    oracle = np.asarray(bnn.forward([w], packets, thresholds=thresholds))
    assert oracle[:, 0].all() and not oracle[:, 1].any()
    lp = prog.lower()
    for backend in BACKENDS:
        np.testing.assert_array_equal(
            executor.execute(lp, packets, backend=backend), oracle
        )


def test_packed_word_packing_matches_reference_packing():
    """The packed backend's word packing agrees with the numpy word-layout
    reference for ragged shapes."""
    rng = np.random.default_rng(12)
    for m, n in [(1, 1), (13, 45), (256, 32), (7, 96), (300, 17)]:
        bits = rng.integers(0, 2, (m, n)).astype(np.int32)
        k = np.arange(n)
        packed = executor._pack_words(
            jnp.asarray(bits, jnp.uint32),
            jnp.asarray(k // 32),
            jnp.asarray(k % 32, jnp.uint32),
            -(-n // 32),
        )
        np.testing.assert_array_equal(np.asarray(packed), pack_bit_rows(bits))


def test_opcode_runs_cover_all_elements():
    case = ProgramCase((32, 64, 32), 0, "default", 0)
    lp = build_case(case).lowered
    runs = lp.opcode_runs()
    assert runs[0][0] == 0 and runs[-1][1] == lp.num_elements
    for (_, stop_a, _), (start_b, _, _) in zip(runs, runs[1:]):
        assert stop_a == start_b
    # Within a run's rows, only that run's opcodes (plus pads) appear.
    for start, stop, used in runs:
        present = set(np.unique(lp.opcode[start:stop]).tolist())
        assert present <= set(used)


# ---------------------------------------------------------------------------
# Multi-tenant: random tenant mixes on the packed path
# ---------------------------------------------------------------------------

@given(
    program_cases(max_layers=2, max_width=24),
    program_cases(max_layers=3, max_width=16),
    stream_plans(max_packets=200, max_chunk=64),
)
@settings(max_examples=HEAVY_EXAMPLES)
def test_fuzz_multitenant_packed_bit_exact(case_a, case_b, plan):
    """Random tenant mixes through merged and time-sliced modes on the
    packed path: every tenant's outputs equal its single-program run."""
    n, chunk, seed = plan
    with artifact_on_failure(
        "fuzz_multitenant_packed_bit_exact", (case_a, case_b, plan)
    ):
        builts = [build_case(case_a), build_case(case_b)]
        from repro.core.pipeline import ChipSpec

        chip = ChipSpec(num_elements=512, phv_bits=1 << 16, name="fuzz-big")
        rng = np.random.default_rng(seed)
        width = max(b.program.input_bits for b in builts)
        tids = rng.integers(0, len(builts), n).astype(np.int32)
        bits = rng.integers(0, 2, (n, width)).astype(np.int32)
        singles = [
            executor.execute(
                b.lowered,
                bits[np.nonzero(tids == t)[0], : b.program.input_bits],
                backend="jnp",
            )
            for t, b in enumerate(builts)
        ]
        for mode in ("merged", "time_sliced"):
            sched = SwitchScheduler(chip, mode=mode, quantum=max(1, chunk))
            for t, b in enumerate(builts):
                sched.admit(b.program, name=f"t{t}")
            res = sched.run(
                (tids, bits),
                mode=mode,
                backend="packed",
                chunk_size=chunk,
                collect=True,
            )
            for t in range(len(builts)):
                np.testing.assert_array_equal(
                    res.outputs_for(t),
                    singles[t],
                    err_msg=f"mode {mode!r} tenant {t} diverges",
                )


@given(tenant_mixes(max_tenants=4))
@settings(max_examples=HEAVY_EXAMPLES)
def test_fuzz_tenant_mix_all_schedules_agree(mix: TenantMixCase):
    """The five-way equivalence on random tenant mixes: merged-interleave
    == merged-concat == time-sliced == the per-tenant single-program
    executor == the ``bnn.forward`` oracle, on the jnp and packed
    backends alike (pcap-backed tenants included)."""
    with artifact_on_failure("fuzz_tenant_mix_all_schedules_agree", mix):
        from repro.core.pipeline import ChipSpec

        builts = [build_case(c) for c in mix.cases]
        tids, bits = mix_traffic(mix)
        chip = ChipSpec(
            num_elements=1024,
            phv_bits=1 << 16,
            max_parallel_ops=1 << 12,
            name="fuzz-mix",
        )
        sched = SwitchScheduler(chip, quantum=max(1, mix.chunk))
        for t, b in enumerate(builts):
            sched.admit(b.program, name=f"t{t}")
        singles = []
        for t, b in enumerate(builts):
            mine = bits[tids == t][:, : b.program.input_bits]
            want = executor.execute(b.lowered, mine, backend="jnp")
            np.testing.assert_array_equal(
                want, _oracle(b, mine),
                err_msg=f"tenant {t} single-program run diverges from oracle",
            )
            singles.append(want)
        schedules = (
            ("merged", "interleave"),
            ("merged", "concat"),
            ("time_sliced", None),
        )
        for backend in ("jnp", "packed"):
            for mode, layout in schedules:
                res = sched.run(
                    (tids, bits),
                    mode=mode,
                    merged=layout,
                    backend=backend,
                    chunk_size=mix.chunk,
                    collect=True,
                )
                assert res.mode == mode
                if mode == "merged":
                    assert res.merged_layout == layout
                for t in range(mix.num_tenants):
                    np.testing.assert_array_equal(
                        res.outputs_for(t),
                        singles[t],
                        err_msg=(
                            f"backend {backend!r} {mode}/{layout} tenant "
                            f"{t} diverges from its single-program run"
                        ),
                    )


@given(program_cases(max_layers=2, max_width=24), chip_specs())
def test_fuzz_admission_is_typed(case: ProgramCase, chip):
    """Random chip budgets either admit a tenant or raise AdmissionError —
    the scheduler never half-admits."""
    with artifact_on_failure("fuzz_admission_is_typed", (case, chip)):
        built = build_case(case)
        sched = SwitchScheduler(chip, mode="merged")
        try:
            sched.admit(built.program)
        except AdmissionError:
            assert not sched.tenants
            return
        assert len(sched.tenants) == 1


# ---------------------------------------------------------------------------
# pcap featurizer: malformed capture bytes must raise, never mis-featurize
# ---------------------------------------------------------------------------

from repro.dataplane import pcap  # noqa: E402

_SEEDS = st.integers(min_value=0, max_value=2**31 - 1)


def _random_capture_bytes(rng) -> tuple[bytes, str]:
    """A valid capture file in a random on-disk dialect."""
    n = int(rng.integers(1, 12))
    pkts, ts, _ = pcap.synthesize_capture(n, seed=int(rng.integers(1 << 16)))
    fmt = ("classic", "classic_be", "classic_ns", "ng")[int(rng.integers(4))]
    if fmt == "ng":
        return pcap.write_pcapng(pkts, ts), fmt
    data = pcap.write_pcap(
        pkts,
        ts,
        nanosecond=(fmt == "classic_ns"),
        endian=">" if fmt == "classic_be" else "<",
    )
    return data, fmt


def _featurizes_cleanly(cap) -> None:
    feats = pcap.featurize(cap, input_bits=32)
    assert feats.shape == (cap.num_packets, 32)
    assert set(np.unique(feats).tolist()) <= {0, 1}


@given(_SEEDS)
def test_fuzz_pcap_truncation_raises_or_parses_whole_records(seed):
    """Truncated captures either raise PcapFormatError or parse to a valid
    shorter capture that featurizes cleanly; for classic pcap, any cut
    *inside* a record must raise — a half record never becomes features."""
    with artifact_on_failure("fuzz_pcap_truncation", seed):
        rng = np.random.default_rng(seed)
        data, fmt = _random_capture_bytes(rng)
        boundaries = None
        if fmt != "ng":
            boundaries, off = {24}, 24
            # caplen field sits 8 bytes into each 16-byte record header
            endian = ">" if fmt == "classic_be" else "<"
            import struct

            while off < len(data):
                caplen = struct.unpack_from(endian + "I", data, off + 8)[0]
                off += 16 + caplen
                boundaries.add(off)
        cuts = set(int(c) for c in rng.integers(0, len(data), 10))
        cuts |= {0, 1, 4, 23, 24, len(data) - 1}
        for cut in sorted(cuts):
            try:
                cap = pcap.read_pcap(data[:cut])
            except pcap.PcapFormatError:
                continue
            _featurizes_cleanly(cap)
            if boundaries is not None:
                assert cut in boundaries, (
                    f"{fmt}: mid-record cut at {cut} parsed silently"
                )


@given(_SEEDS)
def test_fuzz_pcap_mutation_never_escapes_typed_error(seed):
    """Byte-flipped captures either raise PcapFormatError — from the parser
    or the featurizer (e.g. a flipped linktype field) — or still featurize
    to a well-formed {0,1} matrix.  No other exception type, no hang, no
    silent garbage features."""
    with artifact_on_failure("fuzz_pcap_mutation", seed):
        rng = np.random.default_rng(seed)
        data, _ = _random_capture_bytes(rng)
        for _ in range(10):
            blob = bytearray(data)
            for _ in range(int(rng.integers(1, 8))):
                blob[int(rng.integers(len(blob)))] = int(rng.integers(256))
            try:
                _featurizes_cleanly(pcap.read_pcap(bytes(blob)))
            except pcap.PcapFormatError:
                continue


@given(_SEEDS)
def test_fuzz_pcap_garbage_raises(seed):
    """Pure random bytes never parse: wrong magic, short files, and noise
    all surface as PcapFormatError."""
    with artifact_on_failure("fuzz_pcap_garbage", seed):
        rng = np.random.default_rng(seed)
        for length in (0, 3, 4, 16, 64, 500):
            blob = rng.integers(0, 256, length, dtype=np.uint8).tobytes()
            with pytest.raises(pcap.PcapFormatError):
                pcap.read_pcap(blob)
