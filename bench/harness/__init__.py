"""The benchmark's own code: inputs, traffic, reference, trace reduction.

Nothing here is imported by the planner. What the planner's own code
also does (workflow motifs, the cluster table, HEFT, power profiles) is
a frozen copy, so that a change to the program cannot change the
yardstick it is measured by.
"""
