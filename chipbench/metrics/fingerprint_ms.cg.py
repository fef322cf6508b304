"""Per SpMV answered, the session's hashing of the host CSR input
(``session.fingerprint`` spans), in milliseconds: the reading of
``fingerprint_ms.solve``."""

from chipbench.run import load_reader

read = load_reader("fingerprint_ms.solve")
