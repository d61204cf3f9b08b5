"""Engine loop iterations per plan evaluated (``MCResult.n_steps``, the
program's loop counter), averaged over the run's requests."""


def read(run):
    records = getattr(run.cell, "records", None)
    if not records:
        return None
    steps = [res["n_steps"] for rec in records
             for res in rec["results"].values()]
    return sum(steps) / len(steps)
