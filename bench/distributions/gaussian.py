"""Spider's ``gaussian`` distribution (Vu, Migliorini, Eldawy, Belussi,
"Spatial Data Generators", SpatialGems 2019, section 3): each
coordinate of a point normal with mean 0.5 and standard deviation
0.1.  Spider draws again any point that falls outside the unit
square; since the coordinates are independent, that is each
coordinate's normal truncated to [0, 1], drawn here directly (one
point in about a million would fall outside)."""
import jax


def centres(key, n: int):
    return 0.5 + 0.1 * jax.random.truncated_normal(key, -5.0, 5.0, (n, 2))
