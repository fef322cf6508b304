"""Share of the HBM roofline one SpMV of the CG cell reaches, in percent:
the reading of ``spmv_roofline.solve`` (compulsory bytes over the device
time of the whole SpMV program)."""

from chipbench.run import load_reader

read = load_reader("spmv_roofline.solve")
