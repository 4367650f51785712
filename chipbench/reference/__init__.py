"""Plain float32 references, one module per model family
(``chipbench/reference/<family>.py``), found by the family's name."""

import importlib


def family_module(family: str):
    return importlib.import_module(f"chipbench.reference.{family}")
