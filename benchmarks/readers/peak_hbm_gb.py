"""HBM on the fullest chip when the window closes (``harness.window_bytes``:
buffers in use plus the runtime's reservation for programs' temporaries)."""


def read(run, obs, spec):
    peak = obs.values.get("peak_bytes_window")
    return peak / 1e9 if peak else None
