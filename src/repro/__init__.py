"""repro: jax_pallas reproduction of "Practically and Theoretically Efficient
Garbage Collection for Multiversioning".

Written for the installed jax (0.9.0).  Importing ``repro`` touches no device.
"""
