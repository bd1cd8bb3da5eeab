"""Work of one edge-group occupancy call, from the algorithm and not from
any implementation of it: each of the C cells' values is added into its
group's total once (C additions), and the algorithm reads the values
(4 bytes each) and the group ids (4 bytes each) and writes each cell's
group total (4 bytes each): 12·C bytes."""


def cost(shapes: dict) -> dict:
    c = int(shapes["cells"])
    return {"flops": c, "bytes": 12 * c}
