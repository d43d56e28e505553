"""Serving engine: of an expert layer's experts, the share a decode step
read the weights of, mean over every step the replica has read
(`moe_experts_hit_total / (num_experts x moe_layer_steps_total)` of
/v1/metrics once the run's last stream has ended, so ramp and drain are
in it: the fast few-rider steps there pull it under the window's own
share; the step counts the experts with at least one row on the device and
the engine reads the count back with the step's tokens). It describes the
traffic: how many streams ride a step. Moves itl_p95_ms."""


def read(run):
    eng = run.engine or {}
    layer_steps = eng.get("moe_layer_steps_total")
    if not layer_steps or eng.get("moe_experts_hit_total") is None:
        return None
    return 100.0 * eng["moe_experts_hit_total"] / (
        run.config["num_experts"] * layer_steps)
