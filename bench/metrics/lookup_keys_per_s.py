"""Keys answered by the lookup calls that started in the window, over the
span from the first call's start to the last one's end."""


def read(run):
    calls = run.calls_in_window(run.lookups)
    if not calls:
        return None
    span = max(c[1] for c in calls) - min(c[0] for c in calls)
    return sum(c[3] for c in calls) / span
