"""The whole step's share of the chips' bf16 peak: the matmul operations
the step requires (``flops/<family>.py``, recomputation not counted),
times the steps completed in the window, over its seconds, the chips and
the peak."""


def read(run):
    if not run.steps or run.window_s <= 0:
        return None
    return (100.0 * run.flops_per_step * run.steps / run.window_s
            / run.chips / run.peak["bf16_flops_per_s"])
