"""Test only: a per-layer metric a later PR adds as a file of its own,
which ``run.py`` finds by its name in the manifest without any edit."""


def read(run):
    return float(len(run["closed_steps"])) if run.get("closed_steps") \
        else None
