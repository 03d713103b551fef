"""One module per model family: how a configuration file becomes the
program's model, the family's plain float32 reference forward, and the
shape facts ``lib/flops_bytes.py`` counts with.  ``run.py`` finds a
builder by the ``builder`` key of the configuration file."""
