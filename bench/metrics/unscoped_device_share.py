"""Share of the traced window's busy device time in operations that no
phase names (``bench/harness/scopes.py``): XLA's own copies, ops outside
the differentiated step, ops in no matched module.  None off a trace."""


def read(ctx):
    scopes = getattr(ctx, "scopes", None)
    if scopes is None or not scopes["busy_s"]:
        return None
    return 100.0 * scopes["phases_s"]["unscoped"] / scopes["busy_s"]
