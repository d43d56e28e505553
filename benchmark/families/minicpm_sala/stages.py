"""The device stages of the `minicpm_sala` family's programs, as the
program names them (tony_tpu/models/sala.py, ops/sparse_attention.py,
ops/lightning.py), and what the family's per-layer readers
(benchmark/metrics/) make of a traced run with them: a stage's device time
in one decode step, and a prefill kernel's share of its roofline over the
traced admissions. Reading the profile is lib/stages.py's; no jax here.
"""

from __future__ import annotations

from lib import peaks, stages, traffic

# in one pass over the profile; a name that is the start of another stands
# after it
STAGES = ("tony_sparse_prefill_select", "tony_sparse_select",
          "tony_sparse_attn", "tony_sparse_read",
          "tony_lightning_chunk", "tony_lightning_step")
SPARSE_KERNEL, LIGHTNING_KERNEL = "tony_sparse_attn", "tony_lightning_chunk"
ADMIT_PROGRAM = "jit__admit_step"
# queries one call of `tony_sparse_attn` serves (ops/sparse_attention.py
# PREFILL_CHUNK; tests/test_benchmark_families.py holds the two equal): a
# traced admission's prompt length is told by how many calls a sparse layer
# made. Should they differ, no length of the mix fits the counts any more,
# and the two rooflines are left out, not misread.
SPARSE_CALL_QUERIES = 1024


def ms_per_step(run, stage: str):
    return stages.stage_ms_per_step(run, STAGES, stage)


def whole_layers(admit: dict, lengths) -> tuple:
    """(prompt length, {kernel: (seconds in its whole layers or calls, how
    many)}) of one traced admission, which the profile's end may have cut.
    A sparse layer is a run of the sparse kernel's calls and is whole if a
    lightning call follows it; a lightning call is whole if anything
    follows it. The prompt length is the one of the mix whose layers make
    as many calls as the longest whole run; None if there is none."""
    kernels = [(st, d) for st, d in admit["ops"]
               if st in (SPARSE_KERNEL, LIGHTNING_KERNEL)]
    runs, run = [], []
    for st, d in kernels:
        if st == SPARSE_KERNEL:
            run.append(d)
        elif run:
            runs.append(run)
            run = []
    by_calls = {-(-seq // SPARSE_CALL_QUERIES): seq for seq in lengths}
    seq = by_calls.get(max((len(r) for r in runs), default=0))
    whole = [r for r in runs if seq and by_calls.get(len(r)) == seq]
    light = [d for st, d in kernels[:-1] if st == LIGHTNING_KERNEL]
    return seq, {SPARSE_KERNEL: (sum(map(sum, whole)), len(whole)),
                 LIGHTNING_KERNEL: (sum(light), len(light))}


def kernel_roofline(run, kernel: str, counts_prefix: str):
    """(share %, which bound) of a prefill kernel over the traced
    admissions' whole layers: the least time the chip could take for
    them, by the family's `<counts_prefix>_flops(cfg, seq)` and
    `_bytes(cfg, seq)` (one layer's calls for a prompt of `seq` tokens),
    over the device time they took."""
    admits = (stages.of(run, STAGES) or {}).get(ADMIT_PROGRAM, [])
    if not admits or run.device.get("platform") != "tpu":
        return None
    pk, counts = peaks.peaks_of(run.device["kind"]), run.family.counts
    least = took = 0.0
    bound = set()
    for a in admits:
        seq, whole = whole_layers(a, traffic.prompt_lengths(run.mix))
        seconds, layers = whole[kernel]
        if seq is None or not layers:
            continue
        by_ops = getattr(counts, counts_prefix + "_flops")(
            run.config, seq) / pk["bf16_flops_per_s"]
        by_bytes = getattr(counts, counts_prefix + "_bytes")(
            run.config, seq) / pk["hbm_bytes_per_s"]
        bound.add("compute" if by_ops >= by_bytes else "memory")
        least += layers * max(by_ops, by_bytes)
        took += seconds
    return (100.0 * least / took, "/".join(sorted(bound))) if took else None
