"""Host-patched escapes (``LookupResult.fallbacks``) per 10^6 keys looked
up, over the lookup calls that started in the window."""


def read(run):
    calls = run.calls_in_window(run.lookups)
    rows = sum(c[3] for c in calls)
    if not rows:
        return None
    return 1e6 * sum(c[4] for c in calls) / rows
