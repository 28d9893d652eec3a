"""Harness pieces shared by every cell: generators, reference, yardstick."""
