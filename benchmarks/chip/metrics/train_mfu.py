"""Continual step: model FLOPs of the forward and backward pass on the rows
trained per step (new and replayed), times steps per second of the window,
over the chips' bf16 peak. Recomputation (remat) is not counted."""


def read(run):
    if run["peaks"] is None or run["window_s"] <= 0 or not run["steps"]:
        return None
    achieved = run["steps"] * run["flops_per_step"] / run["window_s"]
    return 100.0 * achieved / (run["n_chips"] * run["peaks"]["bf16_flops_per_s"])
