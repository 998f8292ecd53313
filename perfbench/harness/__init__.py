"""The on-chip benchmark of the served HTTP path.

Everything that measures lives here, apart from the program: traffic
generation, the HTTP load generator, the reduction of a profiler trace
to metrics, the chip peaks, the operation and byte counts of kernels,
the weights, and the plain reference that decides ``correct``. The
program under test is imported from ``src/`` and driven only through
its serving entry points.
"""
