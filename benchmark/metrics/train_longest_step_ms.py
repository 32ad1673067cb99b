"""Longest gap between two step completions in the whole window, on the
host clock: what a stall looks like. In a traced run the gap in which the
profiler started is the benchmark's own and is left out."""

TIMING = True


def read(run):
    done = run["step_done_s"]
    own = run.get("feed_call_at_s")
    gaps = [b - a for a, b in zip(done, done[1:])
            if not (own and a <= own[1] and b >= own[0])]
    return max(gaps) * 1e3 if gaps else None
