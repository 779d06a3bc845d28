"""The benchmark's harness: everything here is the yardstick, nothing is the
program. Only ``program.py`` imports the system under test."""
