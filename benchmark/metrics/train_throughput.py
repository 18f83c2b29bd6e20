"""Items (tokens or images) completed in the window, over the window's
seconds, over the chips: all the work and all the time between the first
and the last ready stamp.  The stamps are those the loop takes itself,
one each time a step's loss is ready (``runners/train.py:_loop``); the
step still in flight at the loop's exit is drained after the window and
is no part of it (its delay is ``notes.drain_ms``): between the loop's
exit and that moment the host does other things, and a stamp taken there
read a host stall as the window's last step."""


def read(run):
    stamps = run.get("stamps")
    if not stamps or len(stamps) < 2:
        return None
    steps = len(stamps) - 1
    return (steps * run["items_per_step"]
            / (stamps[-1] - stamps[0]) / run["chips"])
