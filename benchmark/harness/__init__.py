"""The benchmark's own yardstick: clock, result line, peaks, operation counts,
trace reduction, seeded weights, and the steering of the program from this
process. Nothing here is read by the program; later PRs may not edit it."""
