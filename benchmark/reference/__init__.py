"""One file per model class: the plain float32 reference, the mapping
from the source's config keys to the program's config, and the class's
attention arithmetic for the roofline functions."""
