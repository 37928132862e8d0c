"""Batched big-integer kernels: radix-256 limb ladders in plain XLA.

``ops`` is the public entry (radix-2^16 limbs in and out); ``common`` holds
the limb arithmetic and the Barrett ladders, ``montgomery`` the REDC
ladders, and ``ref`` chooses among them by ladder and reduction.
"""
