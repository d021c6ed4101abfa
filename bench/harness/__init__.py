"""The benchmark's own code: the run driver, traffic, trace reduction,
statistics and the correctness comparison. It imports the system under
test (``repro``) only to drive it."""
