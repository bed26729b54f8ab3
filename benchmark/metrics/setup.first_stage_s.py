"""Seconds from the start of the process's first ``fit.call`` to the end of
its first group's ``input.h2d``: the producer's start, the host slots'
allocation and first touch, the cast and the put."""
import startup_reduce

read = startup_reduce.first_stage_s
