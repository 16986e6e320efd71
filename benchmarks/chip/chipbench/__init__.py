"""Chip benchmark of the serving path: traffic, each architecture's
float32 reference and work counts (``archs/``), peaks, the reduction of
traces and of the program's spans, and the check that decides
``correct``.  Nothing here is imported by the program."""
