"""Rows of real requests per coalesced dispatch over the largest bucket:
``coalesced_requests / (coalesced_batches x B)``, from ``ServingMetrics``.
The server counts a dispatch as coalesced only when it holds more than one
request, so lone dispatches are not in either count."""


def read(run, obs, spec):
    batches = obs.values.get("coalesced_batches")
    if not batches:
        return None
    return (100.0 * obs.values["coalesced_requests"]
            / (batches * obs.values["largest_bucket"]))
