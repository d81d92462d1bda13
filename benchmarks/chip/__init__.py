"""The chip benchmark: cells that serve a model through the program's
``make_serve_fns`` on a TPU, and the yardstick that measures them.  See
``harness.py`` for how a cell is put together and ``run.py`` to run one."""
