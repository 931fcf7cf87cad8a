"""General drivers, one per kind of traffic: a traffic file names its
driver (`"driver": "serve"` or `"train"`), the driver reads the rest of the
file's parameters and the configuration's sizes."""
