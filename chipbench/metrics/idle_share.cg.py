"""Share of the CG cell's traced window in which no op ran on the device, in
percent: the reading of ``idle_share.solve``."""

from chipbench.run import load_reader

read = load_reader("idle_share.solve")
