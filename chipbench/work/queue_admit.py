"""Work of admitting one tick's A arrival lanes into C per-cell FIFO
rings, from the algorithm: read each lane's request id and cell (8·A
bytes), read each cell's queue head and length (8·C), write the lengths
back (4·C), write one admitted flag per lane (A) and one ring slot per
lane (4·A, a bound: only admitted lanes write).  13·A + 12·C bytes; no
floating-point operations."""


def cost(shapes: dict) -> dict:
    a, c = int(shapes["lanes"]), int(shapes["cells"])
    return {"flops": 0, "bytes": 13 * a + 12 * c}
