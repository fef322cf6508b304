"""Device time per SpMV of the Mosaic kernels, in milliseconds: the reading
of ``kernel_ms.solve``, taken in the CG cell."""

from chipbench.run import load_reader

read = load_reader("kernel_ms.solve")
