"""Distribution substrate: sharding specs, cross-device and cross-process
collective helpers (compat.py), fault tolerance, gradient compression, and
the DLRM-style embedding exchange.

Modules here are imported by the launchers (launch/cells.py, launch/train.py)
and by the training loop; they contain no model code.
"""
