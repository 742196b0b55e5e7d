"""tile_occupancy.bitlinear: sub-array tiles that hold lanes of a GEMM,
over the tiles its waves execute (waves x slots from the program's
schedule), summed over every K chunk of the window."""


def read(r):
    executed = r.window.total("tiles_executed")
    if not executed:
        return None
    return 100.0 * r.window.total("tiles_occupied") / executed
