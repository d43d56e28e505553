"""The `minicpm_sala` family (layers of two kinds, a cache by layer kind)
through the harness: rehearsed on the CPU at the tiny size of
tests/data/configs/sala-tiny-serve.json to `correct: true`, a broken timed
path and a state kept in bfloat16 told, and its counts against a hand
count at the served size."""

import json
import os
import subprocess
import sys

import pytest
from conftest import BENCH, REHEARSE, ROOT, rehearsal_line

from lib import spec


@pytest.fixture(scope="module")
def rehearse(tmp_path_factory):
    """A rehearsal BENCHMARK file with one more configuration and cell,
    written beside nothing that is there."""
    tmp = tmp_path_factory.mktemp("sala")
    with open(REHEARSE) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "sala-tiny-serve", "source": "none", "reduced": [],
        "file": "benchmark/tests/data/configs/sala-tiny-serve.json",
        "why": "layers of two kinds"})
    bench["workloads"].append({
        "name": "sala-tiny", "config": "sala-tiny-serve",
        "traffic": "sala-tiny-open", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("sala-tiny")
    path = tmp / "BENCHMARK.sala.json"
    path.write_text(json.dumps(bench))

    def run(*extra, benchmark_file=path, line=True):
        r = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
             "--benchmark-file", str(benchmark_file), "--workload",
             "sala-tiny", "--seed", "3000000041", "--seconds", "2",
             "--trace", "0", "--out", str(tmp / "out"), *extra],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=900)
        return rehearsal_line(r) if line else r
    run.benchmark_file, run.tmp = path, tmp
    return run


def test_the_family_is_rehearsed_to_correct(rehearse):
    got = rehearse()
    assert got["correct"] is True and got["failed"] == 0, got["stdout"]
    assert got["attempted"] > 0
    assert "compare served_logit_gap " in got["stdout"]
    assert "compare resident_bytes_gap " in got["stdout"]


def test_a_broken_timed_path_is_not_correct(rehearse):
    got = rehearse("--sabotage", "flip")
    assert got["correct"] is False
    assert [ln for ln in got["stdout"].splitlines()
            if ln.startswith("compare served_logit_gap ") and "FAIL" in ln]


def test_the_trainers_sabotage_gives_a_served_family_no_result(rehearse):
    """`--sabotage noop` is the train worker's: the replica's launcher
    refuses it, the job ends, and the run prints no result line at all."""
    r = rehearse("--sabotage", "noop", line=False)
    assert r.returncode not in (0, 3), r.stdout[-2000:]
    assert "rehearsal " not in r.stdout and '"correct": true' not in r.stdout


def test_a_state_kept_in_bfloat16_is_told_by_the_replicas_bytes(rehearse):
    """The family's control (families/minicpm_sala/control.py): the same
    cell on a configuration whose `run.program.state_dtype` is bfloat16.
    The served tokens stay inside their limit; `resident_bytes_gap` does
    not, by the half of the states' bytes that went."""
    family = os.path.join(BENCH, "families", "minicpm_sala")
    control = spec._load_module("sala_control",
                                os.path.join(family, "control.py"))
    path = control.write(str(rehearse.benchmark_file), "sala-tiny",
                         str(rehearse.tmp / "control"))
    with open(os.path.join(rehearse.tmp, "control", "configs",
                           "sala-tiny-serve.json")) as f:
        cfg = json.load(f)
    assert cfg["run"]["program"] == {"state_dtype": "bfloat16"}
    sound = rehearse()["stdout"]
    got = rehearse(benchmark_file=path)
    assert got["correct"] is False

    def gap(out):
        ln = [ln for ln in out.splitlines()
              if ln.startswith("compare resident_bytes_gap ")][-1]
        return float(ln.split("value=")[1].split()[0]), "FAIL" in ln

    # half of 18 float32 states of 4 x 16 x 16 (6 layers x 3 slots) went:
    # 36 864 of the 2.11 MB the configuration states
    assert gap(sound) == (pytest.approx(0.0, abs=1e-3), False)
    assert gap(got["stdout"]) == (pytest.approx(36864 / 2114176, rel=0.05),
                                  True)
    assert [ln for ln in got["stdout"].splitlines()
            if ln.startswith("compare served_logit_gap ") and " ok" in ln]


def test_the_programs_own_int8_cache_is_refused_for_this_family(rehearse):
    """`--control program-int8-cache`: the cache by layer kind has no int8
    form, and the replica says so instead of serving."""
    path = os.path.join(BENCH, "tests", "data", "configs",
                        "sala-tiny-serve.json")
    with open(path) as f:
        cfg = json.load(f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = (
        "import sys, json; sys.path[:0] = [%r, %r]\n"
        "from lib import spec\n"
        "import jax\n"
        "from tony_tpu.serve.engine import ContinuousBatchingEngine\n"
        "from tony_tpu.models import sala\n"
        "cfg = json.load(open(%r))\n"
        "c = spec.load_family(%r, cfg).program.program_config(cfg)\n"
        "ContinuousBatchingEngine(sala.sala_init(c, jax.random.PRNGKey(0)),"
        " c, n_slots=2, token_budget=64, quant_cache=True)\n"
        % (BENCH, ROOT, path, path))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and "quant_cache" in r.stderr


def _served():
    path = os.path.join(BENCH, "configs", "minicpm-sala-serve.json")
    with open(path) as f:
        cfg = json.load(f)
    return cfg, spec.load_family(path, cfg).counts


def test_counts_against_a_hand_count():
    cfg, counts = _served()
    assert counts.layers(cfg) == (4, 12)
    # a minicpm4 layer: q 16.78M + k, v 2.10M + o 16.78M + gate 16.78M +
    # MLP 201.33M; a lightning layer: q, k, v 50.33M + o + gate + MLP
    assert counts.layer_matmul_params(cfg, "minicpm4") == 253_755_392
    assert counts.layer_matmul_params(cfg, "lightning-attn") == 285_212_672
    assert counts.matmul_params(cfg) == (
        4 * 253_755_392 + 12 * 285_212_672 + 4096 * 73448)
    # 16 slots x 36864 tokens: K and V 2 x 4 x 16 x 2 x 36864 x 128 x 2 B,
    # compressed keys a 16th of one of them, the ring of 32 rows, and 12
    # float32 states of 32 x 128 x 128 a slot
    kv = 2 * 4 * 16 * 2 * 36864 * 128 * 2
    assert counts.cache_bytes(cfg, 16, 36864) == (
        kv + kv // 32 + 4 * 16 * 2 * 32 * 128 * 2
        + 12 * 16 * 32 * 128 * 128 * 4) == 2_895_118_336
    # a decode step with one slot open at 20000 tokens: the weights once,
    # 1250 compressed keys and 2 x 4096 K/V rows a sparse layer, and the
    # 12 states (6.29 MB) read and written
    row = 2 * 128 * 2
    assert counts.decode_step_bytes(cfg, [20000]) == pytest.approx(
        2 * counts.matmul_params(cfg)
        + 4 * row * (1250 + 8192) + 2 * 12 * 32 * 128 * 128 * 4)
    # up to dense_len the whole context is read
    assert counts.decode_step_bytes(cfg, [8192]) - counts.decode_step_bytes(
        cfg, []) == pytest.approx(
            4 * row * (512 + 2 * 8192) + 2 * 12 * 32 * 128 * 128 * 4)


def test_kernel_counts_are_the_least_work():
    cfg, counts = _served()
    # 64 queries of the first block: a triangle; of block b < 64: b whole
    # blocks and the triangle; past it 63 whole blocks and the triangle
    tri = 64 * 65 // 2
    assert counts.attended_pairs(cfg, 64) == tri
    assert counts.attended_pairs(cfg, 128) == 2 * tri + 64 * 64
    n = 12288
    full = sum(min(b, 63) for b in range(n // 64)) * 64 * 64
    assert counts.attended_pairs(cfg, n) == full + (n // 64) * tri
    assert counts.sparse_call_flops(cfg, n) == 4.0 * 32 * 128 * (
        full + (n // 64) * tri)
    assert counts.sparse_call_bytes(cfg, n) == 2 * n * 32 * 128 * 2 \
        + 2 * n * 2 * 128 * 2
    assert counts.lightning_call_flops(cfg, n) == 5.0 * n * 32 * 128 * 128
    assert counts.lightning_call_bytes(cfg, n) == 4 * n * 4096 * 2 \
        + 32 * 128 * 128 * 4
    with pytest.raises(ValueError):
        counts.train_flops_per_token(cfg, 4096)


def _family_stages():
    return spec._load_module("sala_stages", os.path.join(
        BENCH, "families", "minicpm_sala", "stages.py"))


def test_stages_group_operations_by_the_execution_they_ran_in():
    from lib import stages
    names = _family_stages().STAGES
    loaded = {"planes": [{"name": "/device:TPU:0", "modules": [
        ["jit_a", 0.0, 100.0], ["jit_b", 200.0, 50.0],
        ["jit_a", 300.0, 100.0]], "ops": [
        ["x", 10.0, 5.0], ["x", 20.0, 5.0], ["y", 210.0, 7.0],
        ["x", 390.0, 20.0], ["x", 150.0, 3.0]]}]}
    got = stages.group(loaded)
    assert got["jit_a"][0]["stages"] == {"x": [2, pytest.approx(1e-8)]}
    assert got["jit_a"][0]["ops"] == [["x", 5e-9], ["x", 5e-9]]
    assert got["jit_b"][0]["stages"] == {"y": [1, pytest.approx(7e-9)]}
    assert got["jit_a"][1]["stages"] == {"x": [1, pytest.approx(2e-8)]}
    assert stages.stage_of("tony_lightning_step.3", [], ("tony_l",)) \
        == "tony_l"
    assert stages.stage_of("fusion.7", [3, "jit(f)/tony_sparse_select/dot"],
                           names) == "tony_sparse_select"
    assert stages.stage_of("fusion.7", ["jit(f)/mul"], names) is None


def test_an_admission_cut_by_the_profiles_end_counts_by_its_whole_layers():
    """Two sparse layers of 12 calls (a 12288-token prompt), three
    lightning calls after each; then the profile ends inside the third
    sparse layer."""
    stages = _family_stages()
    S, L = stages.SPARSE_KERNEL, stages.LIGHTNING_KERNEL
    layer = [[S, 0.01]] * 12 + [[L, 0.002]] * 3
    cut = {"ops": layer + layer + [[S, 0.01]] * 5}
    lengths = [12288, 16384, 24576, 32768]
    seq, got = stages.whole_layers(cut, lengths)
    assert seq == 12288
    assert got == {S: (pytest.approx(0.24), 2), L: (pytest.approx(0.012), 6)}
    # a whole admission: its last lightning call has nothing after it and
    # is left out, as a cut one's would have to be
    assert stages.whole_layers({"ops": layer * 4}, lengths)[1] == {
        S: (pytest.approx(0.48), 4), L: (pytest.approx(0.022), 11)}
    # nothing whole: no length is told
    assert stages.whole_layers({"ops": [[S, 0.01]] * 7}, lengths)[0] is None
