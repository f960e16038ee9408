"""device_idle_pct (device trace): 1 - busy / traced span on the busiest
chip, x 100; busy is the union of its operations' intervals."""

from benchmark import profile_trace


def read(run):
    data = run.trace
    if data is None or not data.chips:
        return None
    busy = max(profile_trace.busy_ns(data, c) for c in data.chips)
    span = data.window[1] - data.window[0]
    return 100.0 * (1.0 - busy / span)
