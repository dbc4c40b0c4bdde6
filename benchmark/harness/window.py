"""The closed loop: steps dispatched back to back, a host read of the loss
every `sync_every` steps as a job that logs would do, the window closed by
a last read once the steps dispatched will have taken `seconds`. Throughput
is counted between the first and the last read.

The host runs ahead of a device-bound step, so "now" says little about
where the device is: when the host notices `seconds` have passed, up to
`sync_every` steps are still queued (8 s of them in the dp4 cell, PERF.md
PR 22), and every run of every later check would pay them. So the loop
closes on an estimate: the steps dispatched since the last read end about
that read's time plus their count times the mean step time of the window
so far. A host-bound loop's estimate is "now"."""
import dataclasses
import math
import time
import traceback


@dataclasses.dataclass
class Window:
    steps: int            # completed between the first and the last sync
    seconds: float        # last sync - first sync
    attempted: int        # steps dispatched
    failed: int           # steps that raised or whose loss was not finite
    sync_times: list      # perf_counter of every sync, the opening one first
    losses: list          # the loss read at every sync
    compiles: int         # compile requests inside the window

    @property
    def steps_per_s(self):
        return self.steps / self.seconds if self.seconds > 0 else 0.0


def warm_up(job, steps):
    """Run every shape the window will use and drain the device."""
    for _ in range(steps):
        job.step()
    return job.sync()


def run(job, seconds, sync_every, counter=None, max_steps=None):
    """One window. `max_steps` bounds it by steps instead of by time (the
    traced window). The caller has drained the device (warm_up)."""
    c0 = counter.requests if counter is not None else 0
    t0 = time.perf_counter()
    syncs, losses = [t0], []
    attempted = failed = done = since_sync = 0
    step_s = 0.0              # mean seconds a step, from the reads so far
    while True:
        try:
            job.step()
        except Exception:      # noqa: BLE001 - counted, and the window ends
            traceback.print_exc()
            attempted += 1
            failed += 1
            break
        attempted += 1
        since_sync += 1
        if max_steps is not None:
            over = attempted >= max_steps
        else:
            ends = max(time.perf_counter(), syncs[-1] + since_sync * step_s)
            over = ends - t0 >= seconds
        if since_sync >= sync_every or over:
            loss = job.sync()
            now = time.perf_counter()
            step_s = (now - t0) / attempted
            if math.isfinite(loss):
                done += since_sync
            else:
                failed += since_sync
            syncs.append(now)
            losses.append(loss)
            since_sync = 0
            if over or (max_steps is None and now - t0 >= seconds):
                break
    c1 = counter.requests if counter is not None else 0
    return Window(steps=done, seconds=syncs[-1] - syncs[0],
                  attempted=attempted, failed=failed, sync_times=syncs,
                  losses=losses, compiles=c1 - c0)
