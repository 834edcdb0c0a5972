"""The general harness: cell lookup, the traffic generator, the hub and
client processes, the correctness check and the trace reduction."""
