"""Model FLOP/s utilization: tokens per second through the feeder, times
the operations the forward and backward passes need per token (the family's
function; recomputation not counted), over chips times the peak."""


def read(run, obs, spec):
    if run.peaks is None or "train_tokens_per_s" not in obs.values:
        return None
    per_token = run.cell.family.train_flops_per_token(run.cell.config,
                                                      obs.values["seq"])
    return (100.0 * obs.values["train_tokens_per_s"] * per_token
            / (run.cell.chips * run.peaks["bf16_flops_per_s"]))
