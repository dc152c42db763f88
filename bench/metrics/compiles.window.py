"""Executables compiled (not loaded from the persistent cache) inside the
window: the host round loop (``fed/engine.py::run_rounds``) should meet
only shapes that set-up compiled, so this reads 0."""


def read(rec):
    return rec["compiles_window"]
