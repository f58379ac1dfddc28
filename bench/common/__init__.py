"""Shared yardstick: traffic, weights, counts, peaks, trace reduction."""
