"""Drivers: the code that builds the system under test for a cell, warms
it, drives its traffic through the measured window and checks what the
timed path produced.  A generator names its driver."""
