"""Device, seen from the host: mean time from one `launch.device` closing to
the next opening, union over threads (program span). It reads UNDER the
device's own gap between two launches, by what the host cannot see: the
device starts after `launch.device` opens (the dispatch) and has stopped
before it closes (the first host copy back, the fence). The cross-check that
the program's spans and the device trace describe the same time is
`perf/hostplanes.py`'s `launch_latency`, by hand on the xplane: this gap +
its `dispatch_ms` + `fence_ms` = the device's gap."""

from perf.hostspans import metric


def read(run):
    return metric(run, "host.between_launch_ms")
