"""The benchmark's yardstick: discovery by name, the closed-loop window,
seeded inputs, the plain reference and its comparison, the trace
reduction, and the roofline arithmetic.  Nothing here imports the program
except :mod:`yardstick.runner`, which drives it."""
