"""The benchmark's harness: everything that defines the yardstick.

Traffic generation, the wall-clock driver, the reduction from traces,
step and request logs to metrics, the FLOP and byte counts, the peaks
table, the float32 reference and the comparison that decides
``correct`` live here, apart from the program under test.  The program
contributes only the served request path (gateway, router, paged
engine, fused step, kernel) and its counters.
"""
