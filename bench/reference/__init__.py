"""Plain float32 references, one module per architecture, named by the
configuration file's ``reference`` key.  They import nothing of the program."""

import importlib


def load(name: str):
    """The reference module ``bench/reference/<name>.py``."""
    return importlib.import_module(f"bench.reference.{name}")
