"""Drivers, one per kind of deployment, named by a configuration's
``"driver"``."""
