"""Device: 1 - (union of device-op intervals / traced span), in percent."""


def read(run):
    if not run.trace:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
