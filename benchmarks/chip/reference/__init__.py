"""Plain references the benchmark compares the session against.

They import nothing of the system under test and take nothing it made:
their inputs are the benchmark's own generated graph and event batches and
the session seed. ``low=True`` rounds where floating point enters (the
tie-break noise and score, the damping gate, PageRank's shares and sums)
to bfloat16 instead of float32: the control that the comparison must
refuse.
"""
