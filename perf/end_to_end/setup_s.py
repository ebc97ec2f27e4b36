"""Process start to window open: boot, device claim, data directory filled
or recovered, refresh / ANN build, bundle upload, warm-up. (The reference's
answers are not waited for: what the boot leaves of them is worked out once
the window has closed.)"""


def read(run):
    return run.setup_s
