"""Device time per SpMV of the SpMV program's other ops (in the CG cell, the
XLA gather of x), in milliseconds: the reading of ``xla_ms.solve``."""

from chipbench.run import load_reader

read = load_reader("xla_ms.solve")
