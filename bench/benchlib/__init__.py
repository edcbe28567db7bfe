"""The benchmark's own library: lookup by name, traffic, weights, the serve
window, trace reduction, operation/byte counts, device peaks and the
correctness comparison. Nothing here is imported by the system under test.
"""
