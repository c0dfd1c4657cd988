"""Peak device memory on the fullest chip after the window, in GiB: what
the allocator held (`peak_bytes_reserved`), which on this runtime includes
the programs' temporary memory that `peak_bytes_in_use` leaves out."""


def read(obs):
    return obs["peak_bytes"] / 2 ** 30
