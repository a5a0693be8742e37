"""Engine host loop: wall time around ``Engine.tick`` minus the service
durations that the engine logged in that tick, averaged over the window's
ticks (host clock, ``Engine.service_log``)."""


def read(run):
    if not run.ticks:
        return None
    host = [t.end - t.start - sum(ev.duration_s for ev in t.events) for t in run.ticks]
    return sum(host) / len(host) * 1e3
