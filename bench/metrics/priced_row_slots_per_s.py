"""Rows x T of every pricing job completed in the window, over the time
from the window's start to the last completion.  A row is one site under
one Monte-Carlo seed, priced by every lane of the mix (and by the exact
optimum where the mix has it)."""


def read(run):
    w = run.window
    if not w.get("jobs"):
        return None
    return w["jobs"] * w["row_slots_per_job"] / w["window_s"]
