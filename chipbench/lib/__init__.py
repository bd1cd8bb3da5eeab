"""Shared pieces of the chip benchmark: lookup by name, seeds, the fleet
and traffic generators, the trace reduction and the output check."""
