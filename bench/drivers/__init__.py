"""Drivers: one module per way of offering a mix to the system."""
