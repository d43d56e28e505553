"""lib/stepspans.py on a small hand-built trace (tests/data/
stepspans_small.json; below in ms on the host's clock, the device plane
reads 2 ms early): a loop with one step in flight, the profile opening
inside iteration 100 and closing inside iteration 106.

  iteration  dispatches        lands (wait's end)   its program, host clock
  100        (before the open) 99  (3.0)            (before the open)
  101        101 at 3.8        100 (13.0)           2.9 - 12.9
  102        102 at 13.9       101 (23.0)           12.95 - 22.9
  103        103 at 42.1       102 (47.0)           22.95 - 32.9, then an
                                                    admission to 41.5
  104        104 at 47.9       103 (52.6)           42.3 - 52.5
  105        105 at 53.5       104 (62.55)          52.5 - 62.5
  106        106 at 63.5       (after the close)    62.5 - 72.5 (step 105's)

Steps 99 and 100 have no dispatch in the profile and are dropped, though
step 100's program is there; step 105's program has no wait. Four pair.
"""

import copy
import json
import os
import types

import pytest
from conftest import BENCH, ROOT

from lib import hostspans, spec, stepspans

MS = 1e6
TRACED = ("batch_per_step.traced", "decode_hbm_pct.traced",
          "experts_hit_pct.traced", "expert_matmul_roofline.traced")
# an `lfm2_moe` configuration small enough to count by hand: one dense
# conv layer, then an attention and a conv layer with 4 experts, top-2
MOE = {"hidden_size": 8, "moe_intermediate_size": 4, "intermediate_size": 16,
       "num_experts": 4, "num_experts_per_tok": 2, "vocab_size": 32,
       "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
       "conv_L_cache": 3, "num_dense_layers": 1,
       "layer_types": ["conv", "full_attention", "conv"]}
LLAMA = {"hidden_size": 8, "intermediate_size": 16, "vocab_size": 32,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
         "num_hidden_layers": 2}
HBM, FLOPS = 819e9, 197e12


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(BENCH, "tests", "data",
                           "stepspans_small.json")) as f:
        return json.load(f)


def test_each_landed_step_pairs_with_the_program_its_wait_landed(small):
    got = stepspans.reduce(small["trace"], small["staged"])
    assert (got["executions"], got["paired"], got["dropped"]) == (6, 4, 2)
    steps = got["steps"]
    assert [s["step"] for s in steps] == [101, 102, 103, 104]
    # each with its own program's time, not its neighbour's: step 103's
    # is the 10.2 ms one, dispatched an iteration before its wait
    assert [s["device_ms"] for s in steps] == pytest.approx(
        [9.95, 9.95, 10.2, 10.0])
    assert [s["riders"] for s in steps] == [3, 3, 4, 4]
    assert [s["context_rows"] for s in steps] == [3000, 3003, 3390, 3394]
    # what the model counted rides along under the model's own names,
    # and nothing the spans only pair by
    assert [s["moe_experts_hit"] for s in steps] == [6, 7, 8, 8]
    assert [s["moe_rows"] for s in steps] == [12, 12, 16, 16]
    assert all(set(s) == {"step", "device_ms", "stages", "riders",
                          "context_rows", "moe_experts_hit", "moe_rows"}
               for s in steps)
    assert [s["stages"]["tony_expert_matmul"] for s in steps] \
        == [0.008, 0.008, 0.0085, 0.009]
    assert got["riders_mean"] == 3.5


def test_a_step_read_after_an_admission_keeps_its_own_program(small):
    """Iteration 103 carried an admission and its wake-ups held the loop:
    its wait returns 16.1 ms after step 102's program ended and 3.5 ms
    before step 103's will (the slack allows 4). The nearest end is step
    103's, and it names a shift of 2 between dispatches and executions;
    the other three steps name 1 (the profile opened between step 100's
    dispatch and its program), and 1 pairs all four."""
    trace = small["trace"]
    got = stepspans.reduce(trace)
    third = got["steps"][1]
    assert third["step"] == 102 and third["device_ms"] == pytest.approx(9.95)
    assert third["stages"] == {}            # no stage times were given
    # pairing by the dispatch's own iteration (lib/hostspans.py
    # clock_offset since PR 31) crosses its bounds on the same trace
    line = max((ln["events"] for ln in trace["planes"][1]["lines"]), key=len)
    by_step = {}
    for name, s, d, stats in line:
        if name in (hostspans.DISPATCH, hostspans.WAIT):
            by_step.setdefault(stats["step"], {})[name] = (s, s + d)
    by_step = {k: v for k, v in by_step.items() if len(v) == 2}
    runs = sorted((s, s + d) for n, s, d in
                  trace["planes"][0]["lines"][0]["events"]
                  if n.startswith(stepspans.readers.DECODE_PROGRAM))
    crossed = hostspans.clock_offset(by_step, runs)
    assert crossed["offset_low_ms"] > crossed["offset_high_ms"]
    assert crossed["offset_used_ms"] == 0.0


def test_the_offset_the_pairing_measures(small):
    """Every program ends before the wait that lands it returns and
    starts after the dispatch that made it began: the least of the one
    (step 104: 62.55 - 60.5 on the device's clock) and the greatest of
    the other (step 103: 42.1 - 40.3) bound what the device plane's times
    lack, 2.0 here."""
    got = stepspans.reduce(small["trace"])
    assert got["clock_offset_ms"] == pytest.approx(2.05)
    assert got["clock_offset_low_ms"] == pytest.approx(1.8)
    line = stepspans.summary_line(got, offset_used_ms=0.0)
    assert line.startswith("steps_traced ")
    said = json.loads(line[len("steps_traced "):])
    assert "steps" not in said and said["offset_used_ms"] == 0.0
    assert (said["paired"], said["dropped"], said["riders_mean"]) \
        == (4, 2, 3.5)


def test_half_caught_steps_are_dropped_and_counted(small):
    # without the profile's last whole wait, step 104 goes too
    cut = copy.deepcopy(small["trace"])
    line = cut["planes"][1]["lines"][0]["events"]
    line[:] = [e for e in line if not (
        e[0] == hostspans.WAIT and e[3].get("lands") == 104)]
    got = stepspans.reduce(cut, small["staged"])
    assert (got["paired"], got["dropped"]) == (3, 2)
    # a device plane that starts two programs later: the shift is -1,
    # step 101 has no execution, and the stage times no longer line up
    late = copy.deepcopy(small["trace"])
    del late["planes"][0]["lines"][0]["events"][:2]
    got = stepspans.reduce(late, small["staged"])
    assert (got["executions"], got["paired"], got["dropped"]) == (4, 3, 3)
    assert [s["step"] for s in got["steps"]] == [102, 103, 104]
    assert [s["device_ms"] for s in got["steps"]] == pytest.approx(
        [9.95, 10.2, 10.0])
    assert all(s["stages"] == {} for s in got["steps"])


def test_a_program_without_the_attributes_pairs_nothing(small):
    """The parent of PR 43: the spans say `step` and nothing else."""
    parent = copy.deepcopy(small["trace"])
    for line in parent["planes"][1]["lines"]:
        for e in line["events"]:
            e[3] = {"step": e[3]["step"]}
    got = stepspans.reduce(parent, small["staged"])
    assert (got["executions"], got["paired"], got["dropped"]) == (6, 0, 0)
    assert got["steps"] == [] and got["clock_offset_ms"] is None
    assert got["riders_mean"] is None
    # nor does a profile without a device plane (the CPU rehearsal)
    hosts = {"planes": small["trace"]["planes"][1:]}
    assert stepspans.reduce(hosts)["paired"] == 0


def _run(tmp_path, reduced, config, family, platform="tpu"):
    out = tmp_path / f"out_{family}_{platform}_{len(reduced['steps'])}"
    out.mkdir()
    with open(out / stepspans.OUT_NAME, "w") as f:
        json.dump(reduced, f)
    return types.SimpleNamespace(
        out_dir=str(out), config=config,
        device={"platform": platform, "kind": "TPU v5 lite"},
        family=spec.Family(family, os.path.join(BENCH, "families", family)))


def _reader(name):
    return spec.load_reader(os.path.join(BENCH, "metrics"), name)


def test_the_traced_readers_count_each_step_for_itself(small, tmp_path):
    reduced = stepspans.reduce(small["trace"], small["staged"])
    run = _run(tmp_path, reduced, MOE, "lfm2_moe")
    assert _reader("batch_per_step.traced")(run) == 3.5
    # 4 experts x 2 expert layers a step: 6, 7, 8 and 8 of 8 were hit
    assert _reader("experts_hit_pct.traced")(run) == pytest.approx(
        100 * 29 / 32)
    # an expert is 3 x 8 x 4 = 96 weights; a layer's routed rows are
    # moe_rows / 2 layers, each 4 x (8 + 2 x 4 + 8) bytes of float32
    least = sum(2 * (hit / 2 * 96 * 2 + rows / 2 * 4 * 24) / HBM
                for hit, rows in ((6, 12), (7, 12), (8, 16), (8, 16)))
    took = 0.008 + 0.008 + 0.0085 + 0.009
    assert _reader("expert_matmul_roofline.traced")(run) == pytest.approx(
        100 * least / took)
    assert stepspans.family_reader(run, "expert_roofline")[1] == "memory"
    # shared weights: conv 2 x (3 x 64 + 64), attention 64 + 2 x 32 + 64,
    # the dense MLP 3 x 8 x 16, two routers 2 x 8 x 4, the head 8 x 32
    shared = 2 * 256 + 192 + 384 + 64 + 256
    need = sum(2 * shared + hit * 96 * 2 + 2 * 1 * 1 * 4 * 2 * rows
               + 2.0 * riders * 2 * 3 * 8 * 4
               for hit, rows, riders in ((6, 3000, 3), (7, 3003, 3),
                                         (8, 3390, 4), (8, 3394, 4)))
    assert _reader("decode_hbm_pct.traced")(run) == pytest.approx(
        100 * need / 0.0401 / HBM)
    assert run.step_spans["paired"] == 4        # read once, kept on the run


def test_a_family_that_counts_no_experts_reads_its_context_alone(small,
                                                                 tmp_path):
    reduced = stepspans.reduce(small["trace"])
    for s in reduced["steps"]:
        del s["moe_experts_hit"], s["moe_rows"]
    run = _run(tmp_path, reduced, LLAMA, "llama")
    # a layer: q 64 + k, v 2 x 32 + o 64 + the MLP 3 x 128; the head 256
    weights = 2 * (64 + 64 + 64 + 384) + 256
    need = sum(2 * weights + 2 * 2 * 1 * 4 * 2 * rows
               for rows in (3000, 3003, 3390, 3394))
    assert _reader("decode_hbm_pct.traced")(run) == pytest.approx(
        100 * need / 0.0401 / HBM)
    assert _reader("batch_per_step.traced")(run) == 3.5
    assert _reader("experts_hit_pct.traced")(run) is None
    assert _reader("expert_matmul_roofline.traced")(run) is None


def test_a_family_whose_bytes_are_by_slot_is_charged_every_rider(small,
                                                                 tmp_path):
    """`minicpm_sala`'s count is per slot: a lightning state read and
    written and at most topk blocks attended to a rider. A step's spans
    say its riders and the sum of their contexts, so each rider is
    counted at the step's mean context, not one slot at the sum."""
    with open(os.path.join(BENCH, "configs",
                           "minicpm-sala-serve.json")) as f:
        cfg = json.load(f)
    reduced = stepspans.reduce(small["trace"])
    for s in reduced["steps"]:      # contexts past dense_len, as the cell's
        s["context_rows"] *= 20
    run = _run(tmp_path, reduced, cfg, "minicpm_sala")
    counts = run.family.counts
    sc = counts.sparse_config(cfg)
    sparse_layers, _ = counts.layers(cfg)
    row = cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    capped = sc["topk"] * sc["block_size"]
    assert 20 * 3000 / 3 > sc["dense_len"] > capped
    need = sum(
        2 * counts.matmul_params(cfg)
        + sparse_layers * row * (rows / sc["kernel_stride"]
                                 + 2 * capped * riders)
        + riders * 2 * counts.state_bytes(cfg, 1)
        for rows, riders in ((60000, 3), (60060, 3), (67800, 4), (67880, 4)))
    got = _reader("decode_hbm_pct.traced")(run)
    assert got == pytest.approx(100 * need / 0.0401 / HBM)
    # one slot at the summed context would miss two or three riders'
    # states and caps a step
    one_slot = sum(counts.decode_step_bytes(cfg, [rows])
                   for rows in (60000, 60060, 67800, 67880))
    assert need - one_slot == pytest.approx(
        10 * (2 * counts.state_bytes(cfg, 1)
              + sparse_layers * row * 2 * capped))
    assert _reader("experts_hit_pct.traced")(run) is None


@pytest.mark.parametrize("name", TRACED + ("host_idle_pct",))
def test_the_new_readers_read_nothing_where_there_is_nothing(small, tmp_path,
                                                             name):
    """The parent's profile (no attributes: nothing paired), a run that
    took no profile, and a profile from no chip: None, and no raise."""
    parent = stepspans.reduce({"planes": small["trace"]["planes"][:1]})
    run = _run(tmp_path, parent, MOE, "lfm2_moe")
    if name in TRACED:
        assert _reader(name)(run) is None
    untraced = tmp_path / "untraced"
    untraced.mkdir(exist_ok=True)
    assert _reader(name)(types.SimpleNamespace(
        out_dir=str(untraced), device={"platform": "tpu"})) is None
    cpu = _run(tmp_path, stepspans.reduce(small["trace"], small["staged"]),
               MOE, "lfm2_moe", platform="cpu")
    with open(os.path.join(BENCH, "tests", "data",
                           "hostspans_small.json")) as f:
        spans = hostspans.reduce(json.load(f))
    with open(os.path.join(cpu.out_dir, hostspans.OUT_NAME), "w") as f:
        json.dump(spans, f)
    assert _reader(name)(cpu) is None


def test_host_idle_leaves_out_the_wait_for_work(tmp_path):
    """tests/data/hostspans_small.json: 6500 of the 23000 ns window idle,
    none of it under `idle_wait`, which starts after the device's last
    event. With one more program at [26000, 27000) the window grows to
    26000, of which [24000, 26000) is idle too: 600 of it before the
    `idle_wait` opens at 24600 (300 of step 9's emit, 300 under no span)
    and 1400 under it."""
    with open(os.path.join(BENCH, "tests", "data",
                           "hostspans_small.json")) as f:
        small = json.load(f)

    def run_of(trace, name):
        out = tmp_path / name
        out.mkdir()
        with open(out / hostspans.OUT_NAME, "w") as f:
            json.dump(hostspans.reduce(trace), f)
        return types.SimpleNamespace(
            out_dir=str(out), device={"platform": "tpu"})

    read = _reader("host_idle_pct")
    assert read(run_of(small, "as_recorded")) == pytest.approx(
        100 * 6500 / 23000)
    late = copy.deepcopy(small)
    device = late["planes"][0]["lines"]
    device[0]["events"].append(["jit__decode_sample_step(1)", 26000.0,
                                1000.0])
    device[1]["events"].append(["%fusion.1 = bf16[32]{0} fusion()", 26000.0,
                                1000.0])
    run = run_of(late, "idle_wait_inside")
    spans = hostspans.of_run(run)
    assert spans["idle_s"] == pytest.approx(8500e-9)
    assert spans["idle_by_span"][hostspans.IDLE_WAIT] == pytest.approx(
        1400e-9)
    assert read(run) == pytest.approx(100 * 7100 / 26000)


def test_the_five_entries_are_appended_and_each_finds_its_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    new = list(TRACED) + ["host_idle_pct"]
    at = names.index("state_move_pct")
    assert names[at + 1:at + 6] == new
    serving = ["chat-steady", "sala-longdoc", "lfm2-longgen"]
    for m in bench["per_layer"][at + 1:at + 6]:
        assert m["moves"] == "itl_p95_ms" and m["source"] == "device_trace"
        assert m["workloads"] == (["lfm2-longgen"] if "expert" in m["name"]
                                  else serving)
        assert callable(_reader(m["name"]))
    assert not [m for m in spec.metrics_for(bench, "per_layer", "train-4k")
                if m["name"] in new]
