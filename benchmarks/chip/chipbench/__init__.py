"""Chip benchmark of the serving path: traffic, work counts, peaks,
trace reduction, the float32 reference and the check that decides
``correct``.  Nothing here is imported by the program."""
