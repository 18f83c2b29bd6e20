"""Items (tokens or images) completed in the window, over the window's
seconds, over the chips: all the work and all the time between the first
and the last ready stamp."""


def read(run):
    stamps = run.get("stamps")
    if not stamps or len(stamps) < 2:
        return None
    steps = len(stamps) - 1
    return (steps * run["items_per_step"]
            / (stamps[-1] - stamps[0]) / run["chips"])
