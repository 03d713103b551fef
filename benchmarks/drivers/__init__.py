"""One module per kind of run; ``run.py`` finds it by the ``kind`` key of
the cell's traffic file.  Each exposes ``run(ctx) -> dict``."""
