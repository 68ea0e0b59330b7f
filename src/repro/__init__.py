"""Reproduction of "Communication Round and Computation Efficient
Exclusive Prefix-Sums Algorithms (for MPI_Exscan)" as a jax/TPU system.
"""
