"""The benchmark's own code: cell specs, traffic, seeded weights, the plain
reference, the engine driver, and the reductions from a device trace and a
host record to metrics.  Nothing here is imported by the program."""
