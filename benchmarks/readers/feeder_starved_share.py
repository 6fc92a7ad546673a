"""Share of the window the training loop waited for a batch:
``PipelineMetrics.consumer_starved_s`` gained in the window / window."""


def read(run, obs, spec):
    if "feeder_starved_s" not in obs.values:
        return None
    return 100.0 * obs.values["feeder_starved_s"] / obs.values["window_s"]
